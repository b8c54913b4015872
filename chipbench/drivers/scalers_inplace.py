"""Driver of the in-place scalers cells.

The timed entry is upstream's own suite (``benchmarks/cb/preprocessing.py``)
on a resident split-0 float32 table: the in-place (``copy=False``) forward and
inverse transformation of ``StandardScaler``, ``MinMaxScaler``,
``MaxAbsScaler`` and ``RobustScaler`` (each
``scaler.inverse_transform(scaler.fit_transform(X))``) and then
``Normalizer(copy=False).fit_transform(X)``, ended when the table and every
fitted attribute are ready.  The table that leaves one solve enters the next:
after the first solve its rows have unit norm.

Everything below ``solve`` is the benchmark's own yardstick and imports
nothing of the program: the data generator (a counter-based hash of (row,
column, seed), so that any block and any column can be made again from the
seed and a second table never has to be held), the plain reference (the five
scalers by their definitions, block by block, sums added in float64 on the
host, extrema exact, quantiles from a full sort of one column at a time), the
comparison, the lower-precision control, the faults that ``correct`` has to
refuse and the work model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

# Rows a block of the reference holds (52 MB at 50 columns), and the rows whose
# sums it adds in float32 before the host adds them in float64 (hsvd_rank.py).
BLOCK_ROWS = 1 << 18
REF_BLOCK_ROWS = 16384
QUANTILES = (25.0, 50.0, 75.0)
# The altered answer: RobustScaler's upper quantile times 1 + 1e-4, which moves
# ``iqr_`` by 1e-4 x q75 / iqr, over `quantile_rel`'s limit wherever a column's
# upper quartile lies half an interquartile range from zero (most do).
ALTERED_BY = 1e-4
UNRESTORED_COLUMN = 7


# ---------------------------------------------------------------- generator
def _params(seed: int, cols: int) -> dict:
    """A column's location (within two scales of zero), scale (1/4 to 4), kind
    of tail and drift along the rows, and the hash's two keys, from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    k0, k1 = (int(v) for v in rng.integers(0, 2 ** 32, 2, dtype=np.uint64))
    return {
        "k0": np.uint32(k0), "k1": np.uint32(k1),
        "loc": rng.uniform(-2.0, 2.0, cols).astype(np.float32),
        "scale": (2.0 ** rng.uniform(-2.0, 2.0, cols)).astype(np.float32),
        "drift": (rng.choice([-1.0, 1.0], cols) * rng.uniform(0.3, 0.8, cols)).astype(np.float32),
        "kind": (np.arange(cols) % 4).astype(np.int32),
    }


def _mix(h):
    """murmur3's finalizer: every input bit reaches every output bit."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _values(p: dict, r, j, rows: int, at=lambda v: v):
    """The table's values at rows ``r`` and columns ``j`` (uint32, broadcast
    against each other), elementwise from (row, column, seed) alone: normal,
    uniform, log-normal and heavy-tailed columns by turns, each with its own
    location and scale, the location drifting and the scale growing by half
    along the rows, so that a statistic over half the rows is another one.
    ``at`` picks the columns' parameters that go with ``j``."""
    h = _mix(_mix(r * jnp.uint32(64) + j + p["k0"]) ^ p["k1"])
    # 23 bits and a half: exact in float32, so u never rounds up to 1 (24 bits did, once in 2^24
    # draws, and erf_inv(1) is infinite: some hundred infinities in a table of 1.7e9 values)
    u = ((h >> 9).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)
    z = jnp.float32(np.sqrt(2.0)) * jax.lax.erf_inv(2.0 * u - 1.0)
    kind = at(p["kind"])
    g = jnp.where(kind == 0, z,
                  jnp.where(kind == 1, jnp.float32(np.sqrt(12.0)) * (u - 0.5),
                            jnp.where(kind == 2, jnp.exp(0.5 * z) - jnp.float32(np.exp(0.125)), z * jnp.abs(z))))
    t = r.astype(jnp.float32) * jnp.float32(1.0 / rows)
    return at(p["scale"]) * (1.0 + 0.5 * t) * (at(p["loc"]) + at(p["drift"]) * (2.0 * t - 1.0) + g)


def _block(p: dict, r0, m: int, rows: int):
    """Rows ``r0`` to ``r0 + m`` of the seeded table X_0."""
    cols = p["loc"].shape[0]
    r = jax.lax.broadcasted_iota(jnp.uint32, (m, cols), 0) + jnp.asarray(r0, jnp.uint32)
    return _values(p, r, jax.lax.broadcasted_iota(jnp.uint32, (m, cols), 1), rows)


def _unit_rows(x):
    """``Normalizer``'s definition: every row over its Euclidean norm (1 for a
    row of zeros), in float32 as upstream writes it."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    return x / jnp.where(norm == 0, 1.0, norm), norm[:, 0]


def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    rows = rows or cfg["rows"]
    p = _params(seed, cfg["features"])
    sharding = ht.get_comm().sharding(cfg["split"])
    # one elementwise program with the table's own sharding: a device makes its rows and no others
    table = jax.jit(lambda p: _block(p, 0, rows, rows), out_shardings=sharding)(p)
    x = ht.core.dndarray.DNDarray.from_dense(table, cfg["split"])
    del table
    jax.block_until_ready(x.larray_padded)
    stats = jax.devices()[0].memory_stats()
    # what the table takes as laid out; where the backend reports no memory (the CPU's rehearsal)
    # the buffer's address before and after each store stands in for the peak
    table_bytes = stats["bytes_in_use"] if stats else None
    nb = seeded.blocks(rows, max(1, rows // BLOCK_ROWS))
    return {"x": x, "p": p, "rows": rows, "nb": nb, "table_bytes": table_bytes,
            "sub": seeded.blocks(rows // nb, max(1, rows // nb // REF_BLOCK_ROWS)),
            "notes": {"rows": rows, "features": cfg["features"], "blocks": nb, "table_bytes": table_bytes}}


def _address(x) -> int:
    return x.larray_padded.addressable_shards[0].data.unsafe_buffer_pointer()


def solve(state: dict) -> dict:
    """One solve: upstream's five functions in upstream's order, ended when the
    table and every fitted attribute are ready.  No buffer of the table is held
    here: a reference to it would itself forbid the donation."""
    import heat_tpu as ht

    x, pp, probe = state["x"], ht.preprocessing, state["table_bytes"] is None
    kept = []

    def call(f, arg):
        before = _address(arg) if probe else None
        res = f(arg)
        kept.append(res is arg and (not probe or _address(res) == before))
        return res

    attrs = {}
    for cls in (pp.StandardScaler, pp.MinMaxScaler, pp.MaxAbsScaler, pp.RobustScaler):
        scaler = cls(copy=False)
        call(scaler.inverse_transform, call(scaler.fit_transform, x))
        attrs.update({f"{cls.__name__}.{k}": v.larray_padded for k, v in vars(scaler).items()
                      if k.endswith("_") and hasattr(v, "larray_padded")})
    call(pp.Normalizer(copy=False).fit_transform, x)
    jax.block_until_ready(attrs)
    jax.block_until_ready(x.larray_padded)
    return {"attrs": attrs, "returned_input": all(kept),
            "layout": (x.split, tuple(x.shape), str(x.larray_padded.dtype))}


def work(cfg: dict, rows=None) -> dict:
    """The least any implementation of these calls must do, from the shapes
    alone, in logical and not padded bytes: a fit cannot do with less than one
    read of the values, a transform and its inverse together not with less than
    one read and one write, and the Normalizer needs a read and a write:
    4 x 3 + 2 = 14 passes (93.95 GB at 2^25 x 50, 114.7 ms at 819 GB/s).
    Operations, a value, from the definitions: StandardScaler 4 to fit (mean 1,
    variance 3) + 2 + 2; MinMaxScaler 2 + 2 + 2; MaxAbsScaler 2 + 1 + 1;
    RobustScaler 3 (one compare a quantile, which no selection does with less)
    + 2 + 2; Normalizer 3: 28.  Memory-bound by far."""
    n, f = rows or cfg["rows"], cfg["features"]
    return {"bytes": 14 * n * f * 4, "operations": 28 * n * f}


# ---------------------------------------------------------------- reference
@partial(jax.jit, static_argnames=("m", "rows", "sub"))
def _block_moments(p, r0, mean, m: int, rows: int, sub: int):
    """Of rows ``r0 ...`` of X_1 = unit rows of X_0: the sub-blocks' sums and
    sums of squared distances from ``mean`` (float32, ``sub`` of them), the
    extrema exactly, and the rows' norms."""
    x1, norm = _unit_rows(_block(p, r0, m, rows))
    parts = x1.reshape(sub, m // sub, -1)
    return (jnp.sum(parts, axis=1), jnp.sum((parts - mean) ** 2, axis=1),
            jnp.min(x1, axis=0), jnp.max(x1, axis=0), norm)


@partial(jax.jit, static_argnames=("rows", "ranks"))
def _column_ranks(p, j, norms, rows: int, ranks: tuple):
    """The values of the given ranks in column ``j`` of X_1, from a full sort
    of that one column."""
    col = _values(p, jax.lax.iota(jnp.uint32, rows), jnp.asarray(j, jnp.uint32), rows, at=lambda v: v[j])
    return jnp.sort(col / jnp.where(norms == 0, 1.0, norms))[jnp.asarray(ranks)]


def _linear_ranks(n: int):
    """numpy's ``linear`` rule: the lower ranks then the upper ranks around
    q (n - 1) / 100, and the upper ones' weights."""
    pos = np.asarray(QUANTILES) / 100.0 * (n - 1)
    lo = np.floor(pos).astype(np.int64)
    return tuple(int(r) for r in np.concatenate([lo, np.minimum(lo + 1, n - 1)])), pos - lo


def _interpolated(values, weights):
    """(columns, 6) rank values -> the three quantiles in float64."""
    v = np.asarray(values, np.float64)
    return (v[:, :3] + (v[:, 3:] - v[:, :3]) * weights).T


def reference(state: dict) -> dict:
    """The statistics of X_1, the table as one ``Normalizer`` leaves it: what
    every fit of a later solve sees, up to the round trips' rounding.  Made
    from the seed block by block; the peak at the window's end is read before
    anything is made."""
    stats = jax.devices()[0].memory_stats()
    p, rows, nb, sub = state["p"], state["rows"], state["nb"], state["sub"]
    m = rows // nb
    cols = p["loc"].shape[0]
    zero = jnp.zeros((cols,), jnp.float32)
    first = [_block_moments(p, i * m, zero, m, rows, sub) for i in range(nb)]
    mean = np.sum([np.asarray(b[0], np.float64).sum(axis=0) for b in first], axis=0) / rows
    lo = np.min([np.asarray(b[2], np.float64) for b in first], axis=0)
    hi = np.max([np.asarray(b[3], np.float64) for b in first], axis=0)
    norms = jnp.concatenate([b[4] for b in first])
    del first
    mean32 = jnp.asarray(mean, jnp.float32)
    var = np.sum([np.asarray(_block_moments(p, i * m, mean32, m, rows, sub)[1], np.float64).sum(axis=0)
                  for i in range(nb)], axis=0) / rows
    ranks, weights = _linear_ranks(rows)
    q = _interpolated([np.asarray(_column_ranks(p, j, norms, rows, ranks)) for j in range(cols)], weights)
    return {"mean": mean, "var": var, "min": lo, "max": hi, "q": q, "norms": norms,
            "peak_bytes": stats["peak_bytes_in_use"] if stats else None}


def _expected(ref: dict, feature_range=(0.0, 1.0)) -> dict:
    """Every fitted attribute by its definition, from the reference's
    statistics, with the scale each is compared on: the column's standard
    deviation for what has the data's unit, the value itself for what is a
    ratio, 1 for what lives in the feature range."""
    std = np.sqrt(ref["var"])
    span = np.where(ref["max"] - ref["min"] == 0, 1.0, ref["max"] - ref["min"])
    lo, hi = feature_range
    max_abs = np.maximum(np.abs(ref["min"]), np.abs(ref["max"]))
    iqr = np.where(ref["q"][2] - ref["q"][0] == 0, 1.0, ref["q"][2] - ref["q"][0])
    one = np.ones_like(std)
    return {
        "StandardScaler.mean_": (ref["mean"], std),
        "StandardScaler.var_": (np.where(ref["var"] == 0, 1.0, ref["var"]), ref["var"]),
        "MinMaxScaler.data_min_": (ref["min"], std),
        "MinMaxScaler.data_max_": (ref["max"], std),
        "MinMaxScaler.scale_": ((hi - lo) / span, (hi - lo) / span),
        "MinMaxScaler.min_": (lo - ref["min"] * (hi - lo) / span, one),
        "MaxAbsScaler.max_abs_": (max_abs, max_abs),
        "MaxAbsScaler.scale_": (np.where(max_abs == 0, 1.0, max_abs), max_abs),
        "RobustScaler.center_": (ref["q"][1], std),
        "RobustScaler.iqr_": (iqr, iqr),
    }


@partial(jax.jit, static_argnames=("m", "rows"))
def _block_gaps(table, p, r0, m: int, rows: int):
    """Rows ``r0 ...`` of the final table against X_1's: the largest absolute
    difference, and the largest distance of a row's norm from 1."""
    mine = jax.lax.dynamic_slice_in_dim(table, r0, m, 0)
    x1, _ = _unit_rows(_block(p, r0, m, rows))
    return jnp.max(jnp.abs(mine - x1)), jnp.max(jnp.abs(jnp.sqrt(jnp.sum(mine * mine, axis=1)) - 1.0))


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed solve against the reference: the fitted
    attributes, the table after all the window's round trips, and whether the
    work was done in place."""
    want = _expected(ref)
    rel = {}
    for name, (value, scale) in want.items():
        got = np.asarray(out["attrs"][name], np.float64) if name in out["attrs"] else np.full_like(value, np.inf)
        rel[name] = float(np.max(np.abs(got - value) / scale))
    x = state["x"]
    table = x.larray_padded
    m = state["rows"] // state["nb"]
    gaps = np.asarray([[float(v) for v in _block_gaps(table, state["p"], i * m, m, state["rows"])]
                       for i in range(state["nb"])])
    layout = (x.split, tuple(x.shape), str(table.dtype))
    rms = float(np.sqrt(np.mean(ref["var"] + ref["mean"] ** 2)))
    # the peak at the window's end and the peak now (a fault planted after the reference was
    # made shows only in the second; the reference itself holds some 0.2 GB beside the table)
    stats = jax.devices()[0].memory_stats()
    peak = None if stats is None else max(ref["peak_bytes"], stats["peak_bytes_in_use"])
    in_place = (out["returned_input"] and out["layout"] == layout == (0, (state["rows"], len(ref["mean"])), "float32")
                and (peak is None or peak < 1.25 * state["table_bytes"]))
    return {
        "stat_rel": max(v for k, v in rel.items() if not k.startswith("RobustScaler")),
        "quantile_rel": max(v for k, v in rel.items() if k.startswith("RobustScaler")),
        "x_drift": float(gaps[:, 0].max()) / rms,
        "row_norm_gap": float(gaps[:, 1].max()),
        "inplace_gap": 0 if in_place else 1,
    }


# ------------------------------------------------------------------ control
def _bf16(x):
    """float32 values rounded to bfloat16, as float32.  ``reduce_precision``
    and not a pair of converts: the TPU's compiler may keep the excess
    precision of ``astype(bfloat16).astype(float32)`` inside a fusion (it did,
    PR 33: the control's transforms read as exact as the program's)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, donate_argnums=(0,))
def _affine_low(table, a, b):
    """``x * a + b`` with the values rounded to bfloat16 on the way in: a
    transform in one bfloat16 pass, written over the table."""
    return _bf16(table) * a + b


@jax.jit
def _moments_low(table):
    low = _bf16(table)
    mean = jnp.mean(low, axis=0)
    return mean, jnp.mean((low - mean) ** 2, axis=0), jnp.min(low, axis=0), jnp.max(low, axis=0)


@partial(jax.jit, static_argnames=("ranks",))
def _table_column_ranks_low(table, j, ranks: tuple):
    return jnp.sort(_bf16(jax.lax.dynamic_index_in_dim(table, j, 1, keepdims=False)))[jnp.asarray(ranks)]


@partial(jax.jit, donate_argnums=(0,))
def _unit_rows_low(table):
    low = _bf16(table)
    norm = jnp.sqrt(jnp.sum(low * low, axis=1, keepdims=True))
    return low / jnp.where(norm == 0, 1.0, norm)


def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, with its
    statistics and its transforms in one bfloat16 pass (the values rounded to
    bfloat16 wherever they are read): what `correct` has to refuse.  One solve
    on the resident table, written over it as the program writes, so it leaves
    a table that is no longer X_1: read the faults in a process of their own
    (``chipbench.control --fault-seeds`` without ``--control-seeds``)."""
    import heat_tpu as ht

    split, rows = state["x"].split, state["rows"]
    table = state.pop("x").larray_padded  # the only reference from here on: the passes below donate it
    cols = table.shape[1]
    ranks, weights = _linear_ranks(rows)
    attrs = {}

    def there_and_back(table, a, b):
        return _affine_low(_affine_low(table, a, b), 1.0 / a, -b / a)

    mean, var, lo, hi = _moments_low(table)
    var = jnp.where(var == 0, 1.0, var)
    attrs.update({"StandardScaler.mean_": mean, "StandardScaler.var_": var})
    table = there_and_back(table, 1.0 / jnp.sqrt(var), -mean / jnp.sqrt(var))
    _, _, lo, hi = _moments_low(table)
    scale = 1.0 / jnp.where(hi - lo == 0, 1.0, hi - lo)
    attrs.update({"MinMaxScaler.data_min_": lo, "MinMaxScaler.data_max_": hi, "MinMaxScaler.scale_": scale,
                  "MinMaxScaler.min_": -lo * scale})
    table = there_and_back(table, scale, -lo * scale)
    _, _, lo, hi = _moments_low(table)
    max_abs = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
    attrs.update({"MaxAbsScaler.max_abs_": max_abs, "MaxAbsScaler.scale_": jnp.where(max_abs == 0, 1.0, max_abs)})
    table = there_and_back(table, 1.0 / attrs["MaxAbsScaler.scale_"], jnp.zeros((cols,), jnp.float32))
    q = jnp.asarray(_interpolated([np.asarray(_table_column_ranks_low(table, j, ranks)) for j in range(cols)], weights),
                    jnp.float32)
    iqr = jnp.where(q[2] - q[0] == 0, 1.0, q[2] - q[0])
    attrs.update({"RobustScaler.center_": q[1], "RobustScaler.iqr_": iqr})
    table = there_and_back(table, 1.0 / iqr, -q[1] / iqr)
    table = _unit_rows_low(table)
    state["x"] = ht.core.dndarray.DNDarray.from_dense(table, split)
    del table
    jax.block_until_ready(attrs)
    jax.block_until_ready(state["x"].larray_padded)
    x = state["x"]
    return {"attrs": attrs, "returned_input": True, "layout": (x.split, tuple(x.shape), str(x.larray_padded.dtype))}


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (owner, attribute, maker)}:
    ``maker(original)`` takes the attribute's place.  Read at the cell's own
    size by ``chipbench.control`` and refused at rehearsal size by
    ``chipbench.selftest``."""
    from heat_tpu.core import dispatch, statistics
    from heat_tpu.core.dndarray import DNDarray
    from heat_tpu.preprocessing import preprocessing

    def half(original):  # a fit over the first half of the rows
        def f(self, x, *a, **kw):
            return original(self, x[: x.shape[0] // 2], *a, **kw)
        return f

    def altered(original):  # an answer altered where it is produced: the last requested quantile
        def f(x, q, *a, **kw):
            res = original(x, q, *a, **kw)
            return DNDarray.from_dense(res._dense().at[-1].multiply(1.0 + ALTERED_BY), None, res.device, res.comm)
        return f

    def unrestored(original):  # one column's inverse left out: its scale taken for 1 on the way back
        def f(self, y):
            kept = self.scale_
            self.scale_ = DNDarray.from_dense(kept._dense().at[UNRESTORED_COLUMN].set(1.0), None, kept.device, kept.comm)
            try:
                return original(self, y)
            finally:
                self.scale_ = kept
        return f

    def copied(original):  # a store that does not donate: the proof that the buffer is unshared always fails
        return lambda *a, **kw: False

    # `copied` first: a process's peak never falls again, so where several faults are read in one
    # process (chipbench.control) every later one reads `inplace_gap` 1 as well
    return {"copied": (dispatch, "_refcount_leaf_at_most", copied), "half": (preprocessing.RobustScaler, "_fit", half),
            "altered": (statistics, "percentile", altered),
            "unrestored": (preprocessing.MaxAbsScaler, "_inverse", unrestored)}
