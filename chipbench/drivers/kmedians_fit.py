"""Driver of the KMedians fit cells.

The timed entry is upstream's continuous suite (``benchmarks/cb/cluster.py``,
function ``kmedians``) at a deployment's size:
``ht.cluster.KMedians(n_clusters, init=<seeded DNDarray>, max_iter, tol=-1.0).fit(x)``
on upstream's spherical data set (four unit clusters in 3-D at the port's
centers, cluster after cluster, float32, split 0), ended when the centers, the
labels and the inertia are ready.

Everything below ``solve`` is the benchmark's own yardstick and imports
nothing of the program: the data generator (a counter-based hash of (row,
column, seed), so that any block and any column can be made again from the
seed), the plain reference (exact float32 KMedians: the assignment in row
blocks, every cluster's median of every column from a full sort of that one
column by (label, value), numpy's rule for an even count), the comparison, the lower-precision
control, the faults that ``correct`` has to refuse and the work model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

# The port's centers (heat_tpu/utils/data/spherical.py:21-34, in units of `offset`).
SPHERES = np.array([[-1, -1, -1], [-1, 1, -1], [1, -1, 1], [1, 1, 1]], np.float32)
# Rows a block of the reference's assignment holds.
BLOCK_ROWS = 1 << 20
# The altered answers, each a little over its limit (configs/kmedians-spheres3d.json):
# `altered` scales one coordinate of one center, which lies 4 from zero, by
# 1 + 3e-6: 1.2e-5 against `centers_dist`'s 4e-6; `altered_final` gives
# every 500th row the next cluster's label (2e-3 of the rows against 1e-6)
# and scales the inertia by 1 + 3e-5 (1e-5).
ALTERED_BY = 3e-6
RELABEL_EVERY = 500
INERTIA_BY = 3e-5
WITNESS_ROWS = 4096


# ---------------------------------------------------------------- generator
def _params(seed: int, cfg: dict) -> dict:
    """The hash's two keys and the initial centers (one seeded point of each
    sphere: its center plus N(0, radius^2) a coordinate), from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    k0, k1 = (int(v) for v in rng.integers(0, 2 ** 32, 2, dtype=np.uint64))
    centers = cfg["offset"] * SPHERES[: cfg["clusters"]]
    return {"k0": np.uint32(k0), "k1": np.uint32(k1), "centers": centers, "radius": np.float32(cfg["radius"]),
            "init": (centers + cfg["radius"] * rng.standard_normal(centers.shape)).astype(np.float32)}


def _mix(h):
    """murmur3's finalizer: every input bit reaches every output bit."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _values(p: dict, r, j, rows: int, at=lambda v: v):
    """The table's values at rows ``r`` and columns ``j`` (uint32, broadcast
    against each other), elementwise from (row, column, seed) alone: the
    sphere of the row's block (``rows / clusters`` rows each, cluster after
    cluster) plus a standard normal times the radius.  ``at`` picks the
    centers' coordinates that go with ``j``."""
    h = _mix(_mix(r * jnp.uint32(4) + j + p["k0"]) ^ p["k1"])
    # 23 bits and a half: exact in float32, so u never rounds up to 1 (scalers_inplace.py)
    u = ((h >> 9).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)
    z = jnp.float32(np.sqrt(2.0)) * jax.lax.erf_inv(2.0 * u - 1.0)
    k = p["centers"].shape[0]
    sphere = r // jnp.uint32(rows // k)
    center = at(p["centers"][k - 1])
    for g in range(k - 2, -1, -1):
        center = jnp.where(sphere == g, at(p["centers"][g]), center)
    return center + p["radius"] * z


def _table(p: dict, rows: int):
    shape = (rows, p["centers"].shape[1])
    return _values(p, jax.lax.broadcasted_iota(jnp.uint32, shape, 0), jax.lax.broadcasted_iota(jnp.uint32, shape, 1), rows)


def _refuse_sorting_update(ht) -> None:
    """The configuration's table is a quarter of the chip, and a median taken
    by sorting a masked copy of it (what ``jnp.nanmedian`` compiles to, once a
    cluster an iteration, before PR 37) asks the compiler for many times the
    chip.  A program whose fit loop sorts cannot run the cell, and exits
    here, soon and with a reason, before anything of the cell's size is made
    or compiled: the witness is ``sort`` in the compiled text of the program's
    own loop on a few thousand rows."""
    from heat_tpu.cluster import kmedians

    seen = []
    original = kmedians._kmedians_loop

    def recording(*a, **kw):
        seen.append((a, kw))
        return original(*a, **kw)

    kmedians._kmedians_loop = recording
    try:
        x = ht.array(np.random.default_rng(0).standard_normal((WITNESS_ROWS, 3)).astype(np.float32), split=0)
        ht.cluster.KMedians(n_clusters=2, init="random", random_state=0, max_iter=1, tol=-1.0).fit(x)
    finally:
        kmedians._kmedians_loop = original
    if not seen:
        return
    args, kw = seen[0]
    arrays = [i for i, a in enumerate(args) if hasattr(a, "shape")]  # the rest are the loop's static sizes

    def loop(*given):
        return original(*[given[arrays.index(i)] if i in arrays else a for i, a in enumerate(args)], **kw)

    if " sort(" in jax.jit(loop).lower(*[args[i] for i in arrays]).compile().as_text():
        raise SystemExit("chipbench: this program's KMedians fit loop takes its medians by sorting a masked copy of "
                         "the points; the configuration's table is a quarter of the chip and cannot be sorted there")


def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    _refuse_sorting_update(ht)
    rows = rows or cfg["rows"]
    p = _params(seed, cfg)
    sharding = ht.get_comm().sharding(cfg["split"])
    # one elementwise program with the table's own sharding: a device makes its rows and no others
    table = jax.jit(lambda p: _table(p, rows), out_shardings=sharding)(p)
    from_dense = ht.core.dndarray.DNDarray.from_dense
    x = from_dense(table, cfg["split"])
    del table
    return {"x": x, "init": from_dense(jnp.asarray(p["init"]), None), "p": p, "rows": rows,
            "clusters": cfg["clusters"], "max_iter": cfg["max_iter"],
            "nb": seeded.blocks(rows, max(1, rows // BLOCK_ROWS)),
            "notes": {"rows": rows, "features": cfg["features"], "clusters": cfg["clusters"]}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when every output a user reads is ready."""
    import heat_tpu as ht

    km = ht.cluster.KMedians(n_clusters=state["clusters"], init=state["init"],
                             max_iter=state["max_iter"], tol=-1.0).fit(state["x"])
    out = {"centers": km.cluster_centers_.larray_padded, "labels": km.labels_.larray_padded}
    jax.block_until_ready(out)
    out.update(inertia=km.inertia_, n_iter=km.n_iter_)  # device scalars until read: two fetches
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least work, whatever implements it: an iteration reads the values
    twice (the assignment's read, and one for the update, as if a median cost
    what a mean costs) and writes and reads the labels (int32); the final pass
    reads the values once and writes the labels.  At 2^28 x 3, 5 iterations:
    5 x (6.44 + 2.15) + 3.22 + 1.07 = 47.2 GB, 57.7 ms at 819 GB/s.
    Operations: the assignment's ``3 k f`` a row (subtract, magnitude, add),
    ``max_iter + 1`` times.  A lower bound that no exact selection reaches:
    the share of it says how far a median is from a mean, not how far the
    passes are from the memory."""
    n, f, k, it = rows or cfg["rows"], cfg["features"], cfg["clusters"], cfg["max_iter"]
    return {"bytes": it * (2 * n * f * 4 + 2 * n * 4) + n * f * 4 + n * 4, "operations": (it + 1) * 3 * k * f * n,
            # one counting pass of a selection by group, whatever implements it: the values and the labels read
            # once, and a value asked which digit it holds and whether it counts (two operations)
            "count_pass_bytes": n * f * 4 + n * 4, "count_pass_operations": 2 * n * f}


# ---------------------------------------------------------------- reference
def _bf16(x):
    """float32 values rounded to bfloat16, as float32 (``reduce_precision``:
    a pair of converts is elided inside a TPU fusion, scalers_inplace.py)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@partial(jax.jit, static_argnames=("nb",))
def _assign_blocks(x, centers, nb: int):
    """Every row's nearest center by float32 Manhattan distance, the first on
    ties, block by block: the labels, the clusters' exact counts a block and
    the blocks' sums of squared nearest distances."""
    k = centers.shape[0]
    bs = x.shape[0] // nb

    def one(i):
        blk = jax.lax.dynamic_slice_in_dim(x, i * bs, bs, 0)
        d = jnp.sum(jnp.abs(blk[:, None, :] - centers[None, :, :]), axis=-1)
        label = jnp.argmin(d, axis=1).astype(jnp.int32)
        counts = jnp.sum(label[:, None] == jnp.arange(k, dtype=jnp.int32), axis=0, dtype=jnp.int32)
        return label, counts, jnp.sum(jnp.min(d, axis=1) ** 2)

    labels, counts, inertia = jax.lax.map(one, jnp.arange(nb))
    return labels.reshape(-1), counts, inertia


@partial(jax.jit, static_argnames=("rows", "low"))
def _column_middles(p, j, labels, at, rows: int, low: bool):
    """Every cluster's two middle members in column ``j``: the column, made
    again from the seed, sorted whole by (label, value), so that a cluster's
    members lie side by side in their order; ``at`` (clusters x 2) holds the
    middle ranks' places in that order.  One full sort a column an iteration
    (1.5 s at 2^28 rows, my chip run, PR 37) where a sort a cluster, the
    others' rows sent to +inf, is four (1.06 s each)."""
    col = _values(p, jax.lax.iota(jnp.uint32, rows), jnp.asarray(j, jnp.uint32), rows, at=lambda v: v[j])
    if low:
        col = _bf16(col)
    return jax.lax.sort((labels, col), num_keys=2)[1][at]


def _kmedians(state: dict, low: bool) -> dict:
    """``max_iter`` KMedians iterations from the seeded initial centers and
    one final assignment; an empty cluster keeps its center.  numpy's median:
    the mean of the two middle members, in float64, rounded to float32 as the
    centers are held."""
    x, nb, rows, p = state["x"].larray_padded, state["nb"], state["rows"], state["p"]
    centers = np.asarray(p["init"], np.float32)
    k, f = centers.shape
    for _ in range(state["max_iter"]):
        labels, counts, _ = _assign_blocks(x, jnp.asarray(centers), nb)
        counts = np.asarray(counts, np.int64).sum(axis=0)
        first = np.cumsum(counts) - counts  # where a cluster's members start in the (label, value) order
        held = np.maximum(counts, 1)
        at = np.stack([first + (held - 1) // 2, first + held // 2], axis=1).astype(np.int32)
        mids = np.stack([np.asarray(_column_middles(p, j, labels, at, rows, low), np.float64) for j in range(f)], axis=1)
        new = np.where(counts[:, None] > 0, 0.5 * (mids[:, :, 0] + mids[:, :, 1]), centers)
        centers = new.astype(np.float32)
    labels, _, inertia = _assign_blocks(x, jnp.asarray(centers), nb)
    return {"centers": centers, "labels": labels, "inertia": float(np.asarray(inertia, np.float64).sum()),
            "n_iter": state["max_iter"]}


def reference(state: dict) -> dict:
    return _kmedians(state, low=False)


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed fit against the reference.  Both start from
    the same centers, so center j is compared with center j; the labels are
    held to the exact nearest, by the reference's arithmetic, of the
    PROGRAM's final centers."""
    centers = np.asarray(out["centers"], np.float32)
    exact, _, _ = _assign_blocks(state["x"].larray_padded, jnp.asarray(centers), state["nb"])
    mine = jnp.asarray(out["labels"], jnp.int32)
    off = int(jnp.sum(exact != mine[: exact.shape[0]])) if mine.shape[0] >= exact.shape[0] else exact.shape[0]
    return {
        "centers_dist": float(np.max(np.abs(centers.astype(np.float64) - ref["centers"].astype(np.float64)))),
        "labels_off_share": off / exact.shape[0],
        "inertia_rel": abs(float(out["inertia"]) - ref["inertia"]) / ref["inertia"],
        "n_iter_gap": abs(int(out["n_iter"]) - state["max_iter"]),
    }


# ------------------------------------------------------------------ control
def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, with the
    values rounded to bfloat16 before the selection (the median of rounded
    points is not the median: up to half a bfloat16 step at 4, 2^-6, off):
    what `correct` has to refuse."""
    out = _kmedians(state, low=True)
    return {**out, "centers": jnp.asarray(out["centers"])}


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  Read at the
    cell's own size by ``chipbench.control`` and refused at rehearsal size by
    ``chipbench.selftest``."""
    from heat_tpu.cluster import kmedians

    def half(original):  # the loop on the first half of the rows: two spheres vanish
        def f(xp, centers, *, n_true, **kw):
            return original(xp[: n_true // 2], centers, n_true=n_true // 2, **kw)
        return f

    def unmasked(original):  # the update's group taken as all rows: every center the whole table's median
        def f(xp, centers, **kw):
            real = kmedians._medians

            def of_all_rows(x, labels, c, *a):
                return jnp.broadcast_to(real(x, jnp.zeros_like(labels), c, *a)[:1], c.shape)

            kmedians._medians = of_all_rows
            kmedians._programs.cache_clear()  # traced anew: the programs built so far hold the real update
            try:
                return original(xp, centers, **kw)
            finally:
                kmedians._medians = real
                kmedians._programs.cache_clear()
        return f

    def altered(original):  # an answer altered where it is produced: one coordinate of one center
        def f(xp, centers, **kw):
            new, n_iter, shift = original(xp, centers, **kw)
            return new.at[2, 1].multiply(1.0 + ALTERED_BY), n_iter, shift
        return f

    def altered_final(original):  # the final pass's answers altered where they are produced
        def f(xp, centers, **kw):
            labels, inertia = original(xp, centers, **kw)
            moved = jnp.arange(labels.shape[0]) % RELABEL_EVERY == 0
            return jnp.where(moved, (labels + 1) % centers.shape[0], labels), inertia * (1.0 + INERTIA_BY)
        return f

    return {"half": (kmedians, "_kmedians_loop", half), "unmasked": (kmedians, "_kmedians_loop", unmasked),
            "altered": (kmedians, "_kmedians_loop", altered),
            "altered_final": (kmedians, "_kmedians_assign", altered_final)}
