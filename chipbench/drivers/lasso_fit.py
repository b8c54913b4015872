"""Driver of the Lasso fit cells.

The timed entry is upstream's 2020 suite (``benchmarks/2020/lasso``) and its
demo (``examples/lasso/demo.py``) at the suite's 10^7 samples:
``ht.regression.Lasso(lam, max_iter=100, tol=-1.0).fit(x, y)`` on a resident
float32 table of unit-second-moment columns, split 0, ended when ``theta``
(``coef_`` and ``intercept_``) and ``n_iter`` are ready.

Everything below ``solve`` is the benchmark's own yardstick and imports
nothing of the program: the data generator (a counter-based hash of (row,
column, seed), so that any block can be made again from the seed), the plain
reference (the normal equations from row blocks at ``highest``, summed in
float64 on the host, then cyclic coordinate descent in float64 numpy; and one
pass over the table that goes through no Gram), the comparison, the
lower-precision control, the faults that ``correct`` has to refuse, the work
model, and the witness that sends home a program whose fit loop works on the
table's rows.
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# A float32 dot on the MXU loses part of a long sum (drivers/hsvd_rank.py: at
# `highest` a Gram's diagonal reads within 1.2e-6 over 16,384 rows, 8e-4 low over
# 12.6M), so the reference sums blocks of at most this many rows on the device
# and the blocks in float64 on the host.  Shorter runs inside a block (4,000,
# 2,000, 500 rows) read no nearer (chip run, PERF.md, PR 39).
REF_BLOCK_ROWS = 16384
# The hash's column of y's noise; table column j's own normal is the hash's
# column j + 1 and its left neighbour's is column j, so column 0 leans on a
# neighbour that is no column of the table.
NOISE_COLUMN = 200
# The true model: 16 of the columns carry a coefficient, one in every eight, so
# that no column without one has two neighbours with one.
ACTIVE_EVERY, ACTIVE_FROM, ACTIVE_TO = 8, 1, 7
# `altered` scales the largest coefficient, which is 2 before the shrinkage,
# by 1 + 1e-4: 2e-4 against `coef_dist`'s limit (configs/lasso-1e7x128.json).
ALTERED_BY = 1e-4
# Rows of the witness' table: a prime, so that no other extent of the compiled
# program is this number.
WITNESS_ROWS = 4099


# ---------------------------------------------------------------- generator
def _params(seed: int, cfg: dict) -> dict:
    """The hash's two keys and the true model, from the seed: which column of
    every eight carries a coefficient, the magnitudes (a geometric ladder
    between the configuration's bounds, shuffled) and the signs."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    k0, k1 = (int(v) for v in rng.integers(0, 2 ** 32, 2, dtype=np.uint64))
    f, lo, hi = cfg["features"], *cfg["true_magnitudes"]
    active = np.arange(0, f, ACTIVE_EVERY) + rng.integers(ACTIVE_FROM, ACTIVE_TO, f // ACTIVE_EVERY)
    size = rng.permutation(np.geomspace(lo, hi, active.size)) * rng.choice([-1.0, 1.0], active.size)
    loc = np.where(np.arange(f) % cfg["located_every"] == cfg["located_every"] - 1,
                   cfg["location"] * np.where(np.arange(f) % (2 * cfg["located_every"]) < cfg["located_every"], 1, -1), 0.0)
    return {"k0": np.uint32(k0), "k1": np.uint32(k1), "loc": loc.astype(np.float32),
            # a column's second moment is 1: its location squared and its spread's
            "spread": np.sqrt((1.0 - loc ** 2) / (1.0 + cfg["lean"] ** 2)).astype(np.float32),
            "lean": np.float32(cfg["lean"]), "active": active, "size": size.astype(np.float32),
            "intercept": np.float32(cfg["true_intercept"]), "noise": np.float32(cfg["noise"])}


def _mix(h):
    """murmur3's finalizer: every input bit reaches every output bit."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _normal(p: dict, r, c):
    """A standard normal at row ``r`` and hash column ``c`` (uint32)."""
    h = _mix(_mix(r * jnp.uint32(256) + c + p["k0"]) ^ p["k1"])
    # 23 bits and a half: exact in float32, so u never rounds up to 1 (scalers_inplace.py)
    u = ((h >> 9).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)
    return jnp.float32(np.sqrt(2.0)) * jax.lax.erf_inv(2.0 * u - 1.0)


def _values(p: dict, r, j, loc, spread):
    """The table at rows ``r`` and columns ``j``: the column's location plus
    its spread times (its own normal + ``lean`` times its left neighbour's),
    so that neighbouring columns are correlated and a column's second moment
    is 1, elementwise from (row, column, seed) alone."""
    return loc + spread * (_normal(p, r, j + jnp.uint32(1)) + p["lean"] * _normal(p, r, j))


def _table(p: dict, rows: int):
    shape = (rows, p["loc"].shape[0])
    r, j = (jax.lax.broadcasted_iota(jnp.uint32, shape, d) for d in (0, 1))
    return _values(p, r, j, p["loc"][None, :], p["spread"][None, :])


def _targets(p: dict, rows: int):
    """``y = X theta* + c + noise``, from the hash alone: the sixteen columns
    that carry a coefficient are made again."""
    r = jax.lax.iota(jnp.uint32, rows)
    y = p["intercept"] + p["noise"] * _normal(p, r, jnp.uint32(NOISE_COLUMN))
    for i in range(p["active"].shape[0]):
        k = p["active"][i]
        y = y + p["size"][i] * _values(p, r, k.astype(jnp.uint32), p["loc"][k], p["spread"][k])
    return y[:, None]


# ------------------------------------------------------------------ witness
def _while_closure(text: str) -> str:
    """The text of every computation a ``while`` of the compiled program runs:
    its bodies and conditions and what they call."""
    comps = {m.group(1): m.group(0) for m in re.finditer(r"^(?:ENTRY )?(%[\w.\-]+) \(.*?^\}", text, re.M | re.S)}
    todo = [n for pair in re.findall(r"condition=(%[\w.\-]+), body=(%[\w.\-]+)", text) for n in pair]
    seen = {}
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen[name] = comps[name]
        todo += re.findall(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)", comps[name])
    return "\n".join(seen.values())


def _refuse_rowwise_descent(ht, cfg: dict) -> None:
    """The configuration's table is 5 GB, and a descent that takes a product
    over the table's rows for every coordinate (before PR 39: the whole
    residual, 12,900 times a fit) needs minutes a solve and a second table
    for its column of ones.  A program whose fit loop works on the rows
    cannot run the cell, and exits here, soon and with a reason, before
    anything of the cell's size is made or compiled: the witness is an
    operand of the table's row extent inside a ``while`` of the program's own
    fit, compiled on a few thousand rows."""
    from heat_tpu.regression import lasso

    seen = []
    jitted = {name: fn for name, fn in vars(lasso).items() if hasattr(fn, "lower") and callable(fn)}

    def recording(fn):
        def call(*a, **kw):
            seen.append((fn, a, kw))
            return fn(*a, **kw)
        return call

    for name, fn in jitted.items():
        setattr(lasso, name, recording(fn))
    try:
        rng = np.random.default_rng(0)
        x = ht.array(rng.standard_normal((WITNESS_ROWS, cfg["features"])).astype(np.float32), split=0)
        y = ht.array(rng.standard_normal((WITNESS_ROWS, 1)).astype(np.float32), split=0)
        ht.regression.Lasso(lam=1.0, max_iter=2, tol=-1.0).fit(x, y)
    finally:
        for name, fn in jitted.items():
            setattr(lasso, name, fn)
    rows = re.compile(rf"\[(?:\d+,)*{WITNESS_ROWS}[,\]]")
    for fn, a, kw in seen:
        inside = _while_closure(fn.lower(*a, **kw).compile().as_text())
        if any(rows.search(line) and re.search(r" (?:multiply|dot|convolution)\(", line) for line in inside.splitlines()):
            raise SystemExit("chipbench: this program's Lasso fit loop multiplies operands of the table's row extent "
                             "inside its `while` (a product over the rows for every coordinate); at the configuration's "
                             "10^7 x 128 that is minutes a solve and a second table, and the cell cannot hold one such solve")


# ------------------------------------------------------------------- driver
def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    _refuse_rowwise_descent(ht, cfg)
    rows = rows or cfg["rows"]
    p = _params(seed, cfg)
    comm = ht.get_comm()
    # one elementwise program each with the array's own sharding: a device makes its rows and no others
    table = jax.jit(lambda p: _table(p, rows), out_shardings=comm.sharding(cfg["split"]))(p)
    target = jax.jit(lambda p: _targets(p, rows), out_shardings=comm.sharding(cfg["split"]))(p)
    from_dense = ht.core.dndarray.DNDarray.from_dense
    x, y = from_dense(table, cfg["split"]), from_dense(target, cfg["split"])
    del table, target
    return {"x": x, "y": y, "p": p, "rows": rows, "lam": cfg["lam_per_row"] * rows, "max_iter": cfg["max_iter"],
            "nb": _ref_blocks(rows),
            "notes": {"rows": rows, "features": cfg["features"], "lam": cfg["lam_per_row"] * rows,
                      "active": [int(k) for k in p["active"]]}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when every output a user reads is ready."""
    import heat_tpu as ht

    model = ht.regression.Lasso(lam=state["lam"], max_iter=state["max_iter"], tol=-1.0).fit(state["x"], state["y"])
    out = {"theta": model.theta.larray_padded}
    jax.block_until_ready(out)
    out.update(n_iter=model.n_iter)  # a device scalar until read: one fetch
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least one solve demands of the chip, from the shapes alone: one
    read of the table and of ``y`` (a fit that made ``G`` and ``b`` in one
    pass; at 10^7 x 128: 5.16 GB, 6.3 ms at 819 GB/s); a symmetric Gram, the
    moments and ``max_iter`` sweeps of ``(f + 1)^2`` multiply-adds.  The
    descent's 12,900 dependent turns are in neither bound: the share says how
    far a fit is from what the memory allows."""
    n, f, it = rows or cfg["rows"], cfg["features"], cfg["max_iter"]
    return {"bytes": n * f * 4 + n * 4, "operations": n * f * f + 4 * n * f + 2 * it * (f + 1) ** 2,
            # one read of the table, whatever computes the Gram from it
            "gram_pass_bytes": n * f * 4}


# ---------------------------------------------------------------- reference
def _ref_blocks(rows: int) -> int:
    """The fewest equal row blocks of at most `REF_BLOCK_ROWS` rows."""
    return next(nb for nb in range(-(-rows // REF_BLOCK_ROWS), rows + 1) if rows % nb == 0)


def _bf16(x):
    """float32 values rounded to bfloat16, as float32 (``reduce_precision``:
    a pair of converts is elided inside a TPU fusion, scalers_inplace.py)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("nb", "low"))
def _normal_blocks(x, y, nb: int, low: bool):
    """Per block, the Gram of ``[1, x, y]`` (nb, f + 2, f + 2) at ``highest``:
    ``X^T X`` with the intercept's row of sums and the count, ``X^T y`` and
    ``y^T y`` are parts of it.  ``low``: the values rounded to bfloat16
    first (the control)."""
    bs = x.shape[0] // nb

    def one(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * bs, bs, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, i * bs, bs, 0)
        if low:
            xb, yb = _bf16(xb), _bf16(yb)
        blk = jnp.concatenate([jnp.ones((bs, 1), x.dtype), xb, yb], axis=1)
        return jnp.matmul(blk.T, blk, precision=HIGHEST)

    return jax.lax.map(one, jnp.arange(nb))


def _normal_equations(state: dict, low: bool):
    """(G, b, y^T y) in float64: the blocks summed on the host."""
    a = np.asarray(_normal_blocks(state["x"].larray_padded, state["y"].larray_padded, state["nb"], low),
                   np.float64).sum(axis=0)
    return a[:-1, :-1], a[:-1, -1], a[-1, -1]


def _descend(G, b, lam: float, max_iter: int) -> np.ndarray:
    """``max_iter`` cyclic sweeps in float64 from ``theta = 0``, coordinate 0
    (the intercept) first and not penalized: a coordinate's ``rho`` is what
    its column gives with the residual of all the OTHER coordinates,
    ``b_j - G_j . theta + G_jj theta_j``."""
    theta = np.zeros(b.shape[0])
    for _ in range(max_iter):
        for j in range(b.shape[0]):
            rho = b[j] - G[j] @ theta + G[j, j] * theta[j]
            theta[j] = (rho if j == 0 else np.sign(rho) * max(abs(rho) - lam, 0.0)) / G[j, j]
    return theta


def _objective(G, b, yy, theta, lam: float) -> float:
    return 0.5 * (yy - 2.0 * b @ theta + theta @ G @ theta) + lam * np.abs(theta[1:]).sum()


def reference(state: dict) -> dict:
    G, b, yy = _normal_equations(state, low=False)
    theta = _descend(G, b, state["lam"], state["max_iter"])
    return {"theta": theta, "G": G, "b": b, "objective": _objective(G, b, yy, theta, state["lam"])}


@partial(jax.jit, static_argnames=("nb",))
def _direct_blocks(x, y, theta, nb: int):
    """Through no Gram: per block the residual ``y - theta_0 - x theta[1:]``,
    its sum and its products with the columns (nb, f + 1), and its square's
    sum (nb,)."""
    bs = x.shape[0] // nb

    def one(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * bs, bs, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, i * bs, bs, 0)[:, 0]
        r = yb - theta[0] - jnp.matmul(xb, theta[1:], precision=HIGHEST)
        return jnp.concatenate([jnp.sum(r)[None], jnp.matmul(r, xb, precision=HIGHEST)]), jnp.sum(r * r)

    return jax.lax.map(one, jnp.arange(nb))


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed fit against the reference.  ``kkt_gap`` holds
    the reference's own Gram to a pass that goes through none: at the
    PROGRAM's ``theta``, ``X^T (y - X theta)`` from the table against
    ``b - G theta``, over ``lam``; an error common to the program's Gram and
    the reference's shows there."""
    theta = np.asarray(out["theta"], np.float64).reshape(-1)
    lam = state["lam"]
    pulls, squares = _direct_blocks(state["x"].larray_padded, state["y"].larray_padded,
                                    jnp.asarray(theta, jnp.float32), state["nb"])
    # the pass ran at theta as float32 holds it
    held = np.asarray(jnp.asarray(theta, jnp.float32), np.float64)
    pull = np.asarray(pulls, np.float64).sum(axis=0)
    objective = 0.5 * np.asarray(squares, np.float64).sum() + lam * np.abs(held[1:]).sum()
    return {
        "coef_dist": float(np.max(np.abs(theta - ref["theta"]))),
        "support_gap": int(np.sum((theta[1:] != 0) != (ref["theta"][1:] != 0))),
        "objective_rel": abs(objective - ref["objective"]) / ref["objective"],
        "kkt_gap": float(np.max(np.abs(pull - (ref["b"] - ref["G"] @ held)))) / lam,
        "n_iter_gap": abs(int(out["n_iter"]) - state["max_iter"]),
    }


# ------------------------------------------------------------------ control
def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, with the
    values of the table and of ``y`` rounded to bfloat16 before the Gram:
    what `correct` has to refuse."""
    G, b, _ = _normal_equations(state, low=True)
    return {"theta": jnp.asarray(_descend(G, b, state["lam"], state["max_iter"]), jnp.float32),
            "n_iter": state["max_iter"]}


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  Read at the
    cell's own size by ``chipbench.control`` and refused at rehearsal size by
    ``chipbench.selftest``.  ``half``, ``altered`` and ``no_intercept`` are
    refused by ``coef_dist``, ``unpenalised`` by ``support_gap``."""
    from heat_tpu.regression import lasso

    program = lasso._program  # the jitted program itself: the witness wraps the module's name for a while

    def half(original):  # the second half of the rows counts for nothing: the same lam bites twice as hard
        def f(x, y, *a, n, **kw):
            first = jnp.arange(x.shape[0]) < n // 2  # zeroed, not sliced: over a mesh the rows stay where they lie
            return original(jnp.where(first[:, None], x, 0), jnp.where(first.reshape((-1,) + (1,) * (y.ndim - 1)), y, 0),
                            *a, n=n // 2, **kw)
        return f

    def altered(original):  # an answer altered where it is produced: the largest coefficient
        def f(*a, **kw):
            theta, n_iter, moved = original(*a, **kw)
            return theta.at[jnp.argmax(jnp.abs(theta[1:])) + 1].multiply(1.0 + ALTERED_BY), n_iter, moved
        return f

    def unpenalised(original):  # the threshold taken as 0: least squares, and the support fills
        def f(x, y, lam, *a, **kw):
            return original(x, y, jnp.zeros_like(lam), *a, **kw)
        return f

    def no_intercept(original):  # the columns' sums taken as 0: the intercept sees no column
        def f(*a, **kw):
            real = lasso._normal_equations

            def sums_as_zero(*args, **kwargs):
                # the equations stand about a shift c (lasso._normal_equations): A = G' + drag x G'_0 with
                # G' = [1, x - c]^T [1, x - c].  The table's own sums are G'_0 + n c; taking THEM as 0 in
                # [1, x]^T [1, x] takes v w^T + w v^T from G', v = (1, -c), w = (0, sums)
                A, b, col_sq, drag, cy = real(*args, **kwargs)
                G = A - drag[:, None] * A[0][None, :]
                v, w = (-drag).at[0].set(1), (G[0] + G[0, 0] * drag).at[0].set(0)
                G = G - v[:, None] * w[None, :] - w[:, None] * v[None, :]
                return G + drag[:, None] * G[0][None, :], b, col_sq, drag, cy

            lasso._normal_equations = sums_as_zero
            program.clear_cache()  # traced anew: the programs built so far hold the real sums
            try:
                return original(*a, **kw)
            finally:
                lasso._normal_equations = real
                program.clear_cache()
        return f

    return {"half": (lasso, "_lasso_fit", half), "altered": (lasso, "_lasso_fit", altered),
            "unpenalised": (lasso, "_lasso_fit", unpenalised), "no_intercept": (lasso, "_lasso_fit", no_intercept)}
