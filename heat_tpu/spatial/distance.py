"""Pairwise distance computations, analog of heat/spatial/distance.py.

The reference's ``_dist`` (distance.py:209-747) is an explicit ring: each
of ceil(p/2) rounds sends a standing row-block to rank+iter and computes
one tile, exploiting symmetry when Y is X.  Here the ring is ONE shard_map
program: X's row-block stands still, Y's row-block rides ``lax.ppermute``
around the mesh, and every round contributes one (n/p, m/p) tile — memory
per device is O(nm/p + (n+m)f/p) instead of the full matrix, and the
Y-is-X case computes each off-diagonal tile once and ships its transpose
to the mirror owner, halving the MXU work exactly like the reference.
``cdist_topk`` fuses the ring with a running top-k so KNN never
materializes (n, m) at all — peak memory O(n(k+m/p)/p) per device.
Metrics mirror _euclidian/_gaussian/_manhattan (distance.py:17-135).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..core import types
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["cdist", "cdist_small", "cdist_topk", "manhattan", "rbf"]


def _pairwise_sqeuclidean(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """||x_i - y_j||^2 via the expanded form (one MXU matmul instead of the
    reference's broadcast-subtract tile, distance.py:17)."""
    x_sq = jnp.sum(x * x, axis=1, keepdims=True)
    y_sq = jnp.sum(y * y, axis=1, keepdims=True).T
    cross = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    d = x_sq + y_sq - 2.0 * cross
    return jnp.maximum(d, 0.0)


def _pairwise_direct(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Exact broadcast-subtract form (distance.py:17-40).  More accurate than
    the expanded form for near-duplicate points (no catastrophic cancellation)
    at the cost of an O(n*m*f) intermediate that XLA fuses into the reduce."""
    diff = x[:, None, :] - y[None, :, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


def _pairwise_euclidean(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Expanded-form euclidean (the quadratic_expansion metric)."""
    return jnp.sqrt(_pairwise_sqeuclidean(x, y))


def _pairwise_sqeuclidean_bf16(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Mixed-precision expanded form: the O(n*m*f) cross term runs on
    bf16 operands with **f32 accumulation pinned** via
    ``preferred_element_type`` (the J203 rule's own prescription), while
    the O((n+m)*f) norms stay f32 — rounding enters only through the
    one-time bf16 quantization of the inputs, so the distance error is
    ~1e-2 relative (the KMeans ``tolerance`` policy's contract) for half
    the MXU traffic.  Only reachable under a tolerance-policy predict
    scope (see :func:`cdist`), which also sanctions the narrowing casts
    for the J201 dtype-flow rule."""
    xb = x.astype(jnp.bfloat16)
    yb = y.astype(jnp.bfloat16)
    cross = jnp.matmul(xb, yb.T, preferred_element_type=jnp.float32)
    x_sq = jnp.sum(x * x, axis=1, keepdims=True)
    y_sq = jnp.sum(y * y, axis=1, keepdims=True).T
    d = x_sq + y_sq - 2.0 * cross
    return jnp.maximum(d, 0.0)


def _pairwise_euclidean_bf16(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Mixed-precision expanded-form euclidean (bf16 cross term)."""
    return jnp.sqrt(_pairwise_sqeuclidean_bf16(x, y))


def _active_lowp_dtype():
    """The predict scope's low-precision compute dtype name (None =
    native).  Lazy import: the policy layer sits above core, and the
    query is one contextvar read on the miss-free hot path."""
    from ..analysis import precision_policy as _pp

    return _pp.active_compute_dtype()


def _pairwise_manhattan(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """City-block tile (the reference _manhattan, distance.py:110)."""
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _prep(X: DNDarray, Y: Optional[DNDarray]):
    sanitize_in(X)
    if X.ndim != 2:
        raise NotImplementedError(f"X should be a 2D DNDarray, but is {X.ndim}D")
    if X.split is not None and X.split != 0:
        raise NotImplementedError(f"Splittings other than 0 or None currently not supported, got {X.split}")
    xd = X._dense()
    if not types.heat_type_is_inexact(X.dtype):
        xd = xd.astype(jnp.float32)
    if Y is None:
        return xd, xd
    sanitize_in(Y)
    if Y.ndim != 2:
        raise NotImplementedError(f"Y should be a 2D DNDarray, but is {Y.ndim}D")
    if X.shape[1] != Y.shape[1]:
        raise ValueError(f"X and Y must have the same number of features, got {X.shape[1]} and {Y.shape[1]}")
    yd = Y._dense()
    if not types.heat_type_is_inexact(Y.dtype):
        yd = yd.astype(jnp.float32)
    return xd, yd


def _tile_metric(metric: str, x, y):
    """One (bn, bm) tile of the chosen metric (distance.py:17-135)."""
    if metric == "sqeuclidean":
        return _pairwise_sqeuclidean(x, y)
    if metric == "euclidean":
        return jnp.sqrt(_pairwise_sqeuclidean(x, y))
    if metric == "euclidean_direct":
        return _pairwise_direct(x, y)
    if metric == "manhattan":
        return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)
    raise ValueError(metric)


@functools.lru_cache(maxsize=64)
def _ring_cdist_fn(comm, metric: str, symmetric: bool, bn: int, bm: int, f: int, dtype: str):
    """Jitted ring distance program (reference _dist, distance.py:209-747).

    Per device: the standing X block (bn, f), a circulating Y block
    (bm, f), and the (bn, p*bm) output row-band.  ``symmetric`` runs only
    ceil(p/2) rounds and ppermutes each tile's transpose to its mirror
    owner.  The Python round loop unrolls at trace time, so every
    ppermute has a static permutation.
    """
    p = comm.size
    axis = comm.axis_name
    shift_back = [((i + 1) % p, i) for i in range(p)]  # receive from r+1

    def body(x_blk, y_blk):
        r = jax.lax.axis_index(axis)
        out = jnp.zeros((bn, p * bm), x_blk.dtype)
        y_cur = y_blk
        rounds = (p // 2 + 1) if symmetric else p
        zero = jnp.zeros((), jnp.int32)
        for it in range(rounds):
            j = (r + it) % p  # owner of the block currently held
            tile = _tile_metric(metric, x_blk, y_cur)
            out = jax.lax.dynamic_update_slice(out, tile, (zero, (j * bm).astype(jnp.int32)))
            if symmetric and 0 < it and not (p % 2 == 0 and it == p // 2):
                # mirror tile: rows of owner j, columns of owner r
                perm = [(i, (i + it) % p) for i in range(p)]
                mirror = jax.lax.ppermute(tile.T, axis, perm)
                src = (r - it) % p
                out = jax.lax.dynamic_update_slice(
                    out, mirror, (zero, (src * bm).astype(jnp.int32))
                )
            if it + 1 < rounds:
                y_cur = jax.lax.ppermute(y_cur, axis, shift_back)
        return out

    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
    )


def _ring_eligible(X: DNDarray, Y: Optional[DNDarray]) -> bool:
    return (
        X.split == 0
        and X.comm.size > 1
        and (Y is None or (isinstance(Y, DNDarray) and Y.split == 0 and Y.comm == X.comm))
    )


def _ring_cdist(X: DNDarray, Y: Optional[DNDarray], metric: str) -> DNDarray:
    comm = X.comm
    symmetric = Y is None
    Yr = X if Y is None else Y
    x_blk = X.larray_padded
    y_blk = Yr.larray_padded
    if not types.heat_type_is_inexact(X.dtype):
        x_blk = x_blk.astype(jnp.float32)
    if not types.heat_type_is_inexact(Yr.dtype):
        y_blk = y_blk.astype(jnp.float32)
    if x_blk.dtype != y_blk.dtype:
        y_blk = y_blk.astype(x_blk.dtype)
    p = comm.size
    bn = x_blk.shape[0] // p
    bm = y_blk.shape[0] // p
    fn = _ring_cdist_fn(comm, metric, symmetric, bn, bm, int(X.shape[1]), str(x_blk.dtype))
    out = fn(x_blk, y_blk)  # (n_pad, m_pad) split 0
    m = Yr.shape[0]
    if out.shape[1] != m:
        out = out[:, :m]  # drop Y's padding columns (local slice per shard)
    return DNDarray(out, (X.shape[0], m), types.canonical_heat_type(out.dtype), 0, X.device, comm)


def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Euclidean distance matrix (distance.py:136).

    Row-split inputs on a mesh take the memory-bounded ppermute ring
    (reference distance.py:209-747) — the full matrix exists only
    row-sharded, never per device."""
    if _ring_eligible(X, Y):
        _prep_checks(X, Y)
        # the distributed ring stays f32: its tile/output buffers share
        # the operand dtype, so the mixed-precision variant below (f32
        # accumulation over bf16 operands) applies to the eager tile
        # path only — the one serving's replicated predict batches take
        return _ring_cdist(X, Y, "euclidean" if quadratic_expansion else "euclidean_direct")
    xd, yd = _prep(X, Y)
    # through the executable cache: repeated shapes (iterative fits, the
    # serving layer's bucket-padded predict batches) hit one compiled
    # program instead of paying 4-6 eager jnp launches per call
    from ..core import dispatch

    op = _pairwise_euclidean if quadratic_expansion else _pairwise_direct
    if _active_lowp_dtype() == "bfloat16":
        # a tolerance-policy predict scope (precision_policy.scope +
        # HEAT_TPU_PREDICT_DTYPE=bfloat16) flips the cross term to bf16;
        # the direct metric also takes the expanded form here — its extra
        # cancellation error is far below the scope's declared rtol, and
        # bf16 has no broadcast-subtract MXU path to offer instead
        op = _pairwise_euclidean_bf16
    d = dispatch.eager_apply(op, (xd, yd))
    split = 0 if X.split is not None else None
    return DNDarray.from_dense(d, split, X.device, X.comm)


def _prep_checks(X: DNDarray, Y: Optional[DNDarray]):
    sanitize_in(X)
    if X.ndim != 2:
        raise NotImplementedError(f"X should be a 2D DNDarray, but is {X.ndim}D")
    if Y is not None:
        sanitize_in(Y)
        if Y.ndim != 2:
            raise NotImplementedError(f"Y should be a 2D DNDarray, but is {Y.ndim}D")
        if X.shape[1] != Y.shape[1]:
            raise ValueError(
                f"X and Y must have the same number of features, got {X.shape[1]} and {Y.shape[1]}"
            )


cdist_small = cdist


@functools.lru_cache(maxsize=64)
def _ring_topk_fn(comm, k: int, bn: int, bm: int, m_true: int, dtype: str, lowp: bool = False):
    """Ring distance fused with a running k-smallest merge.

    The (bn, bm) tile of each round merges into a standing (bn, k)
    candidate set — the full (n, m) matrix never exists (reference KNN
    materializes it, kneighborsclassifier.py:114; this is the blocked
    fusion VERDICT r2 #3 asks for).  Returns (distances, global Y row
    indices), both (bn, k) per device.  ``lowp`` swaps the tile's cross
    term to bf16 operands with f32 accumulation (the tolerance-policy
    KNN predict path): the candidate/output buffers stay f32, so only
    the per-round MXU contraction narrows."""
    p = comm.size
    axis = comm.axis_name
    shift_back = [((i + 1) % p, i) for i in range(p)]

    def body(x_blk, y_blk):
        r = jax.lax.axis_index(axis)
        vals = jnp.full((bn, k), jnp.inf, x_blk.dtype)
        idxs = jnp.zeros((bn, k), jnp.int32)
        y_cur = y_blk
        for it in range(p):
            j = (r + it) % p
            if lowp:
                tile = _pairwise_sqeuclidean_bf16(x_blk, y_cur)
            else:
                tile = _tile_metric("sqeuclidean", x_blk, y_cur)
            gcol = j * bm + jnp.arange(bm, dtype=jnp.int32)  # global Y rows
            tile = jnp.where(gcol[None, :] < m_true, tile, jnp.inf)  # pad cols out
            cand_v = jnp.concatenate([vals, tile], axis=1)
            cand_i = jnp.concatenate([idxs, jnp.broadcast_to(gcol, (bn, bm))], axis=1)
            neg_top, pos = jax.lax.top_k(-cand_v, k)
            vals = -neg_top
            idxs = jnp.take_along_axis(cand_i, pos, axis=1)
            if it + 1 < p:
                y_cur = jax.lax.ppermute(y_cur, axis, shift_back)
        return jnp.sqrt(vals), idxs

    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )


def cdist_topk(X: DNDarray, Y: DNDarray, k: int):
    """k smallest Euclidean distances and their Y-row indices per X row.

    Ring-fused on a mesh (peak memory O(n(k + m/p)/p) per device); dense
    distance + top_k otherwise.  Returns ``(dist, idx)`` DNDarrays of
    shape (n, k) with X's split."""
    _prep_checks(X, Y)
    k = int(k)
    if k > Y.shape[0]:
        raise ValueError(f"k={k} exceeds the number of Y rows ({Y.shape[0]})")
    if _ring_eligible(X, Y):
        comm = X.comm
        x_blk = X.larray_padded
        y_blk = Y.larray_padded
        if not types.heat_type_is_inexact(X.dtype):
            x_blk = x_blk.astype(jnp.float32)
        if not types.heat_type_is_inexact(Y.dtype):
            y_blk = y_blk.astype(jnp.float32)
        if x_blk.dtype != y_blk.dtype:
            y_blk = y_blk.astype(x_blk.dtype)
        p = comm.size
        lowp = _active_lowp_dtype() == "bfloat16" and x_blk.dtype == jnp.float32
        fn = _ring_topk_fn(
            comm, k, x_blk.shape[0] // p, y_blk.shape[0] // p, Y.shape[0], str(x_blk.dtype),
            lowp,
        )
        vals, idxs = fn(x_blk, y_blk)
        n = X.shape[0]
        dt = types.canonical_heat_type(vals.dtype)
        return (
            DNDarray(vals, (n, k), dt, 0, X.device, comm),
            DNDarray(idxs, (n, k), types.canonical_heat_type(idxs.dtype), 0, X.device, comm),
        )
    xd, yd = _prep(X, Y)
    if _active_lowp_dtype() == "bfloat16":
        d = _pairwise_sqeuclidean_bf16(xd, yd)
    else:
        d = _pairwise_sqeuclidean(xd, yd)
    neg_top, idx = jax.lax.top_k(-d, k)
    split = 0 if X.split is not None else None
    return (
        DNDarray.from_dense(jnp.sqrt(-neg_top), split, X.device, X.comm),
        DNDarray.from_dense(idx, split, X.device, X.comm),
    )


def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """City-block distance matrix (distance.py:182).

    Ring-scheduled on a mesh like :func:`cdist`."""
    if _ring_eligible(X, Y):
        _prep_checks(X, Y)
        return _ring_cdist(X, Y, "manhattan")
    xd, yd = _prep(X, Y)
    from ..core import dispatch

    d = dispatch.eager_apply(_pairwise_manhattan, (xd, yd))
    split = 0 if X.split is not None else None
    return DNDarray.from_dense(d, split, X.device, X.comm)


def rbf(X: DNDarray, Y: Optional[DNDarray] = None, sigma: float = 1.0, quadratic_expansion: bool = False) -> DNDarray:
    """Gaussian (RBF) kernel matrix exp(-d^2 / (2 sigma^2)) (distance.py:158).

    Ring-scheduled on a mesh; the exp is an elementwise pass over the
    row-sharded result."""
    if _ring_eligible(X, Y):
        _prep_checks(X, Y)
        d2 = _ring_cdist(X, Y, "sqeuclidean")
        out = jnp.exp(-d2.larray_padded / (2.0 * sigma * sigma))
        return DNDarray(out, d2.shape, d2.dtype, 0, X.device, X.comm)
    xd, yd = _prep(X, Y)
    d2 = _pairwise_sqeuclidean(xd, yd)
    k = jnp.exp(-d2 / (2.0 * sigma * sigma))
    split = 0 if X.split is not None else None
    return DNDarray.from_dense(k, split, X.device, X.comm)
