"""QR decomposition, analog of heat/core/linalg/qr.py (qr.py:17-310).

Reference algorithms: split=0 tall-skinny -> TS-QR with a tree merge of
stacked R factors (procs_to_merge fan-in, Demmel et al. 2012, qr.py:64);
split=1 -> block-wise stabilized Gram-Schmidt with Bcasts of the current
column block (qr.py:125-310).

TPU-native:

* split=0: the TS-QR tree is expressed as a shard_map collective program —
  each shard takes a local QR, all-gathers the small R factors over ICI,
  and (redundantly, replicated across shards) merges them with one more
  QR; the local Q is then corrected by its block of the merge Q.  One ICI
  all-gather of p×(n×n) floats replaces the reference's log-p rounds of
  paired send/recvs.  Ragged extents (m % p != 0) are handled by zeroing
  the canonical padding rows inside the kernel — the zero rows drop out of
  both the local QR and the merge, so no gather-and-recompute fallback is
  needed.
* split=1: block modified Gram-Schmidt as a shard_map program.  Round i
  broadcasts device i's freshly orthonormalized column block (a psum of a
  masked operand — the collective form of the reference's Bcast), and
  every later device immediately projects it out of its own columns
  (right-looking update = block MGS, the stabilized ordering).  Padded
  columns are masked to zero so they contribute no spurious projections.

Falls back to a global XLA QR only for wide (m < n) split=1 inputs,
batched inputs, and single-device meshes.
"""

from __future__ import annotations

import collections
from typing import Optional

import functools

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from .. import types
from ..dndarray import DNDarray
from ..sanitation import sanitize_in

__all__ = ["qr"]

QR = collections.namedtuple("QR", "Q, R")

_HI = jax.lax.Precision.HIGHEST


def qr(
    A: DNDarray,
    mode: str = "reduced",
    procs_to_merge: int = 2,
) -> QR:
    """Reduced QR decomposition of a 2-D (or batched) array.

    Returns the namedtuple ``QR(Q, R)``; with ``mode='r'`` the Q factor is
    ``None`` (matching qr.py:33-40).
    """
    sanitize_in(A)
    if mode not in ("reduced", "r"):
        raise ValueError(f"mode must be 'reduced' or 'r', got {mode!r}")
    if A.ndim < 2:
        raise ValueError(f"Array A must be at least two-dimensional, but is {A.ndim}-dimensional")
    if not types.heat_type_is_realfloating(A.dtype) and not types.heat_type_is_complexfloating(A.dtype):
        A = A.astype(types.float32)

    m, n = A.shape[-2], A.shape[-1]
    comm = A.comm
    p = comm.size

    use_tsqr = (
        A.ndim == 2
        and A.split == 0
        and p > 1
        and (comm.padded_extent(m) // p) >= n
    )
    if use_tsqr:
        q_pad, r = _tsqr_shard_map(A, compute_q=(mode == "reduced"))
        R = DNDarray.from_dense(r, None, A.device, A.comm)
        if mode == "r":
            return QR(None, R)
        Q = DNDarray(
            jax.device_put(q_pad, comm.sharding(0)),
            (m, n),
            A.dtype,
            0,
            A.device,
            A.comm,
        )
        return QR(Q, R)

    use_bgs = A.ndim == 2 and A.split == 1 and p > 1 and m >= n
    if use_bgs:
        q_pad, r_pad = _bgs_fn(comm, n, A.larray_padded.shape[1] // p)(A.larray_padded)
        R = DNDarray(
            jax.device_put(r_pad, comm.sharding(1)), (n, n), A.dtype, 1, A.device, A.comm
        )
        if mode == "r":
            return QR(None, R)
        Q = DNDarray(
            jax.device_put(q_pad, comm.sharding(1)), (m, n), A.dtype, 1, A.device, A.comm
        )
        return QR(Q, R)

    # general path: XLA's QR over the (sharded) dense view — wide split=1
    # matrices, batched inputs, and single-device meshes
    dense = A._dense()
    if mode == "r":
        r = jnp.linalg.qr(dense, mode="r")
        return QR(None, DNDarray.from_dense(r, None if A.ndim == 2 else A.split, A.device, A.comm))
    q, r = jnp.linalg.qr(dense, mode="reduced")
    q_split = A.split
    r_split = None if A.ndim == 2 and A.split == 0 else A.split
    if A.ndim == 2 and A.split == 1:
        r_split = 1
    return QR(
        DNDarray.from_dense(q, q_split, A.device, A.comm),
        DNDarray.from_dense(r, r_split, A.device, A.comm),
    )


def _tsqr_shard_map(A: DNDarray, compute_q: bool = True):
    """Single-level TS-QR as a shard_map collective (see module docstring).

    Requires padded_m/p >= n (caller checks).  Ragged true extents are
    masked inside the kernel; fully-padded shards contribute zero R rows
    and produce zero Q rows.
    """
    comm = A.comm
    m = A.shape[0]
    # padding rows are don't-care bytes (zero at creation, but elementwise
    # ops may have mapped them); mask only when padding exists
    m_true = m if comm.pad_amount(m) else 0
    q, r = _tsqr_fn(comm, compute_q, m_true)(A.larray_padded)
    # r is replicated identically on all shards; take it as the global R
    return q, r


@functools.lru_cache(maxsize=64)
def _tsqr_fn(comm, compute_q: bool, m_true: int):
    """Jitted, cached TS-QR executable — rebuilding the shard_map per call
    would retrace (and recompile) on every invocation.  ``m_true > 0`` enables masking of canonical padding
    rows (the ragged case); 0 means the extent divides evenly."""
    mesh = comm.mesh
    axis = comm.axis_name

    def body(a_loc):
        # a_loc: (padded_m/p, n) local block
        rows, n = a_loc.shape
        idx = jax.lax.axis_index(axis)
        if m_true:
            grow = idx * rows + jnp.arange(rows)
            a_loc = jnp.where((grow < m_true)[:, None], a_loc, 0)
        q1, r1 = jnp.linalg.qr(a_loc, mode="reduced")  # (rows, n), (n, n)
        r_all = jax.lax.all_gather(r1, axis, axis=0, tiled=True)  # (p*n, n)
        q2, r2 = jnp.linalg.qr(r_all, mode="reduced")  # (p*n, n), (n, n)
        q2_block = jax.lax.dynamic_slice_in_dim(q2, idx * n, n, axis=0)  # (n, n)
        q_loc = jnp.matmul(q1, q2_block, precision=_HI) if compute_q else q1
        return q_loc, r2

    return jax.jit(
        _shard_map(
            body,
            mesh=mesh,
            in_specs=P(axis, None),
            out_specs=(P(axis, None), P(None, None)),
            # r2 is computed redundantly from the all-gathered R stack, so it
            # is replicated by construction; the static analyzer cannot see
            # through the QR call to prove it
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=64)
def _bgs_fn(comm, n_true: int, nb: int):
    """Jitted, cached split=1 block modified Gram-Schmidt executable.

    The reference's column-block loop (qr.py:220+: current rank takes a
    local QR of its block, Bcasts the Q panel, later ranks subtract the
    projection) becomes p rounds inside one shard_map program:

      round i: every shard runs the local QR (only shard i's result is
      kept), shard i's orthonormal panel Qi is broadcast as
      psum(where(idx==i, Qi, 0)), and shards j>i update
      A_j -= Qi (Qi^T A_j) immediately — the right-looking (block-MGS)
      ordering that keeps the process stabilized.

    Outputs the padded Q (m, p*nb) and R (n_true, p*nb), both split=1.
    """
    mesh = comm.mesh
    axis = comm.axis_name
    p = comm.size

    def body(a_loc):
        # a_loc: (m, nb) local column block
        idx = jax.lax.axis_index(axis)
        gcol = idx * nb + jnp.arange(nb)
        colmask = (gcol < n_true).astype(a_loc.dtype)  # (nb,)
        a_loc = a_loc * colmask[None, :]

        def round_i(i, carry):
            a_cur, q_loc, r_loc = carry
            qi_cand, rii = jnp.linalg.qr(a_cur, mode="reduced")  # (m, nb), (nb, nb)
            # padded input columns give zero R columns, but arbitrary
            # orthonormal Q columns — zero them so they project nothing
            qi_cand = qi_cand * colmask[None, :]
            is_me = (idx == i).astype(a_cur.dtype)
            # Bcast of shard i's panel as a collective sum of masked operands
            qi = jax.lax.psum(qi_cand * is_me, axis)  # (m, nb)
            q_loc = jnp.where(idx == i, qi_cand, q_loc)
            r_loc = jnp.where(
                idx == i,
                jax.lax.dynamic_update_slice_in_dim(r_loc, rii * colmask[None, :], i * nb, 0),
                r_loc,
            )
            # later shards subtract the projection onto Qi right away
            rij = jnp.matmul(qi.T, a_cur, precision=_HI)  # (nb, nb)
            later = idx > i
            rij = jnp.where(later, rij, 0.0)
            a_cur = a_cur - jnp.matmul(qi, rij, precision=_HI)
            r_loc = jnp.where(
                later,
                jax.lax.dynamic_update_slice_in_dim(r_loc, rij, i * nb, 0),
                r_loc,
            )
            return a_cur, q_loc, r_loc

        r0 = jnp.zeros((p * nb, nb), a_loc.dtype)
        _, q_loc, r_loc = jax.lax.fori_loop(
            0, p, round_i, (a_loc, jnp.zeros_like(a_loc), r0)
        )
        # R rows beyond the true column count are zero by construction;
        # drop them so the unsplit row dim has the exact global extent
        return q_loc, r_loc[:n_true]

    return jax.jit(
        _shard_map(
            body,
            mesh=mesh,
            in_specs=P(None, axis),
            out_specs=(P(None, axis), P(None, axis)),
            check_vma=False,
        )
    )
