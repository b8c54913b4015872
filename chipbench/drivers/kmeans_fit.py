"""Driver of the KMeans fit cells.

The timed entry is the public
``ht.cluster.KMeans(n_clusters, init=<seeded DNDarray>, max_iter, tol=-1.0).fit(x)``
on a split-0 float32 ``DNDarray``, ended when the centers, the labels and the
inertia are ready.  Everything below ``solve`` is the benchmark's own
yardstick and imports nothing of the program: the data generator, the plain
reference (exact float32 Lloyd in short row blocks, the blocks' partial sums
added in float64 on the host), the comparison, the control (the reference's
mathematics with the arithmetic the program had before PR 27), the faults that
``correct`` has to refuse and the work model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeded

HIGHEST = jax.lax.Precision.HIGHEST
# A float32 sum over millions of rows reads low on the MXU (hsvd_rank.py), so
# the reference sums short blocks on the device and the blocks in float64 on
# the host.
REF_BLOCK_ROWS = 16384
# The altered answers, each a little over its limit (configs/kmeans-1e8x16.json):
# `altered` moves one coordinate of one center by 1.5 times `centers_dist`'s
# limit, which stays over it where the program's own distance (at most 2.5e-4
# at the cell's size) points the other way; `altered_final` gives every 500th
# row the next cluster's label (2e-3 of the rows, beside the 8e-4 that rounding
# the points moves, against 1.5e-3) and scales the inertia by 1 + 3e-5 (1e-5).
ALTERED_BY = 1.2e-3
RELABEL_EVERY = 500
INERTIA_BY = 3e-5


@partial(jax.jit, static_argnames=("rows", "nb"))
def _make(key, true, rows: int, nb: int):
    """Unit-noise blobs around ``true``, every row's blob drawn uniformly,
    written block by block into one buffer, so that the peak is the array plus
    one block's temporaries."""
    k, f = true.shape
    bs = rows // nb

    def body(i, buf):
        k_blob, k_noise = jax.random.split(jax.random.fold_in(key, i))
        blob = jax.nn.one_hot(jax.random.randint(k_blob, (bs,), 0, k), k, dtype=jnp.float32)
        blk = jnp.matmul(blob, true, precision=HIGHEST) + jax.random.normal(k_noise, (bs, f), jnp.float32)
        return jax.lax.dynamic_update_slice(buf, blk, (i * bs, 0))

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((rows, f), jnp.float32))


# A witness of rounded centers: ``c0``'s first coordinate rounds from 1.003 to
# 1.0 in bfloat16, and the first point, exact in bfloat16 and nearest ``c0``,
# lies far along that coordinate; with the centers rounded in the cross term
# it goes to ``c1`` and pulls that center's first coordinate from 1 to 4.5.
WITNESS_X = np.array([[8.0, -(2.0 ** -8)], [1.0, 1.0], [1.0, -1.0], [1.5, 2.0]], np.float32)
WITNESS_CENTERS = np.array([[1.003, 1.0], [1.0, -1.0]], np.float32)


def _refuse_rounded_centers(ht) -> None:
    """The configuration states its precision: float32 centers in both terms
    of the assignment.  A program whose product rounds the centers on this
    device (the MXU's default, which is what ran before PR 27) cannot run it,
    and exits here, soon and with a reason, rather than time fits whose
    answers the limits refuse."""
    km = ht.cluster.KMeans(n_clusters=2, init=ht.array(WITNESS_CENTERS), max_iter=1, tol=-1.0)
    km.fit(ht.array(WITNESS_X, split=0))
    if float(np.asarray(km.cluster_centers_.larray_padded)[1, 0]) > 2.0:
        raise SystemExit("chipbench: this program's KMeans.fit rounds the centers in the assignment's product on "
                         f"{jax.devices()[0].device_kind}; the configuration states float32 centers in both terms")


def build(cfg: dict, seed: int, rows=None) -> dict:
    import heat_tpu as ht

    _refuse_rounded_centers(ht)
    rows = rows or cfg["rows"]
    f, k = cfg["features"], cfg["clusters"]
    nb = seeded.blocks(rows, 48)
    k_true, k_init, k_data = jax.random.split(seeded.key(seed), 3)
    true = 0.5 * jax.random.normal(k_true, (k, f), jnp.float32)
    init = true + 0.25 * jax.random.normal(k_init, (k, f), jnp.float32)
    from_dense = ht.core.dndarray.DNDarray.from_dense
    x = from_dense(_make(k_data, true, rows, nb), cfg["split"])
    return {"x": x, "init": from_dense(init, None), "clusters": k, "max_iter": cfg["max_iter"],
            "ref_nb": seeded.blocks(rows, max(1, rows // REF_BLOCK_ROWS)),
            "notes": {"rows": rows, "features": f, "clusters": k, "blocks": nb}}


def solve(state: dict) -> dict:
    """One solve: the public call, ended when every output a user reads is ready."""
    import heat_tpu as ht

    km = ht.cluster.KMeans(n_clusters=state["clusters"], init=state["init"],
                           max_iter=state["max_iter"], tol=-1.0).fit(state["x"])
    out = {"centers": km.cluster_centers_.larray_padded, "labels": km.labels_.larray_padded}
    jax.block_until_ready(out)
    out.update(inertia=km.inertia_, n_iter=km.n_iter_)  # device scalars until read: two fetches
    return out


def work(cfg: dict, rows=None) -> dict:
    """The least one fit demands of the chip at the stated precision, from the
    shapes alone.  The assignment's product takes the points in bfloat16, so
    the least traffic reads ``x`` once (4 bytes a value) and writes its
    bfloat16 copy (2), then streams that copy once for each of the
    ``max_iter`` iterations (assignment and update of one iteration can share
    one stream: a row's label needs only that row) and once more for the final
    assignment, which writes every row's label (int32).  At 10^8 x 16, 30
    iterations: 6.4 + 3.2 + 30 x 3.2 + 3.2 + 0.4 = 109.2 GB.  PR 24 counted
    105.6 GB, without the final pass and its labels; they are results of
    ``fit`` (``labels_``, ``inertia_``), so they are in.  Operations: the two
    products of an iteration (``x c^T`` and ``onehot^T x``, 2 n f k each) and
    the final assignment's one.  Memory-bound by a wide margin (133 ms
    against 8 ms on a v5e)."""
    n, f, k, it = rows or cfg["rows"], cfg["features"], cfg["clusters"], cfg["max_iter"]
    return {"bytes": n * f * 4 + n * f * 2 + (it + 1) * n * f * 2 + n * 4,
            "operations": (2 * it + 1) * 2 * n * f * k}


# ---------------------------------------------------------------- reference
def _half_d2(blk, centers, low: bool):
    """``|c|^2 - 2 x.c`` for a block of rows: float32 at ``highest``, or for
    the control the arithmetic the program had before PR 27: ``|c|^2`` from
    the float32 centers, the cross term from operands rounded to bfloat16."""
    c2 = jnp.sum(centers * centers, axis=1)
    if low:
        xc = jnp.matmul(blk.astype(jnp.bfloat16), centers.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
    else:
        xc = jnp.matmul(blk, centers.T, precision=HIGHEST)
    return c2[None, :] - 2.0 * xc


@partial(jax.jit, static_argnames=("nb", "low", "final"))
def _lloyd_blocks(x, centers, nb: int, low: bool, final: bool = False):
    """One assignment of every row to its nearest center, block by block:
    per block the clusters' sums (nb, k, f) and counts (nb, k); from the
    ``final`` pass instead every row's label and the blocks' inertia (nb,)."""
    k = centers.shape[0]
    bs = x.shape[0] // nb

    def one(i):
        blk = jax.lax.dynamic_slice_in_dim(x, i * bs, bs, 0)
        d = _half_d2(blk, centers, low)
        label = jnp.argmin(d, axis=1).astype(jnp.int32)
        if final:
            return label, jnp.sum(jnp.sum(blk * blk, axis=1) + jnp.min(d, axis=1))
        onehot = jax.nn.one_hot(label, k, dtype=jnp.float32)
        return jnp.matmul(onehot.T, blk, precision=HIGHEST), jnp.sum(onehot, axis=0)

    first, second = jax.lax.map(one, jnp.arange(nb))
    return (first.reshape(-1), second) if final else (first, second)


def _lloyd(state: dict, low: bool) -> dict:
    """``max_iter`` Lloyd iterations from the seeded initial centers and one
    final assignment; an empty cluster keeps its center."""
    x, nb = state["x"].larray_padded, state["ref_nb"]
    centers = np.asarray(state["init"].larray_padded, np.float64)
    for _ in range(state["max_iter"]):
        sums, counts = _lloyd_blocks(x, jnp.asarray(centers, jnp.float32), nb, low)
        sums, counts = np.asarray(sums, np.float64).sum(axis=0), np.asarray(counts, np.float64).sum(axis=0)
        centers = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1.0)[:, None], centers)
    labels, inertia = _lloyd_blocks(x, jnp.asarray(centers, jnp.float32), nb, low, final=True)
    return {"centers": centers, "labels": labels, "inertia": float(np.asarray(inertia, np.float64).sum()),
            "n_iter": state["max_iter"]}


def reference(state: dict) -> dict:
    return _lloyd(state, low=False)


@partial(jax.jit, static_argnames=("nb",))
def _labels_off(x, centers, labels, nb: int):
    """How many rows carry another label than their exact nearest center's."""
    bs = x.shape[0] // nb

    def body(i, off):
        blk = jax.lax.dynamic_slice_in_dim(x, i * bs, bs, 0)
        lab = jax.lax.dynamic_slice_in_dim(labels, i * bs, bs, 0)
        return off + jnp.sum(jnp.argmin(_half_d2(blk, centers, False), axis=1) != lab)

    return jax.lax.fori_loop(0, nb, body, jnp.zeros((), jnp.int32))


def compare(state: dict, out: dict, ref: dict) -> dict:
    """Numbers of the last timed fit against the reference.  Both start from
    the same centers, so center j is compared with center j."""
    centers = np.asarray(out["centers"], np.float64)
    x = state["x"].larray_padded
    off = _labels_off(x, jnp.asarray(centers, jnp.float32), jnp.asarray(out["labels"], jnp.int32), state["ref_nb"])
    return {
        "centers_dist": float(np.max(np.linalg.norm(centers - ref["centers"], axis=1))),
        "inertia_rel": abs(float(out["inertia"]) - ref["inertia"]) / ref["inertia"],
        "labels_off_share": int(off) / x.shape[0],
        "n_iter_gap": abs(int(out["n_iter"]) - state["max_iter"]),
    }


# ------------------------------------------------------------------ control
def control(state: dict) -> dict:
    """The reference's mathematics put in the program's place, with the
    arithmetic the program had on the chip before PR 27: what `correct` has
    to refuse."""
    return _lloyd(state, low=True)


# ------------------------------------------------------------------- faults
def faults() -> dict:
    """Faults planted under the timed path, {name: (module, attribute,
    maker)}: ``maker(original)`` takes the attribute's place.  Read at the
    cell's own size by ``chipbench.control`` and refused at rehearsal size by
    ``chipbench.selftest``.  One chip, and every fit starts from the seeded
    centers, so a left-out exchange and a state kept from fit to fit are not
    faults this cell can have."""
    from heat_tpu.cluster import kmeans

    def altered(original):  # an answer altered where it is produced: one coordinate of one center
        def f(xp, centers, *a, **kw):
            new, n_iter, shift = original(xp, centers, *a, **kw)
            return new.at[3, 5].add(ALTERED_BY), n_iter, shift
        return f

    def altered_final(original):  # the final pass's answers altered where they are produced
        def f(xp, centers, n_true, k):
            labels, new, shift, inertia = original(xp, centers, n_true, k)
            moved = jnp.arange(labels.shape[0]) % RELABEL_EVERY == 0
            return jnp.where(moved, (labels + 1) % k, labels), new, shift, inertia * (1.0 + INERTIA_BY)
        return f

    def half(original):  # the second half of the rows left out of the fit; the final pass labels them all
        def f(xp, centers, n_true, *a, **kw):
            return original(xp[: n_true // 2], centers, n_true // 2, *a, **kw)
        return f

    return {"altered": (kmeans, "_lloyd_loop", altered), "altered_final": (kmeans, "_lloyd_step", altered_final),
            "half": (kmeans, "_lloyd_loop", half)}
