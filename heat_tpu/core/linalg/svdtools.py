"""Hierarchical / randomized SVD, analog of heat/core/linalg/svdtools.py.

Reference: ``hsvd_rank`` (svdtools.py:46), ``hsvd_rtol`` (:130), core
``hsvd`` (:256-473) — a level-wise merge tree over ranks: each rank takes a
local truncated SVD of its column block, dimensions are allgathered, and
groups of ``no_of_merges`` blocks are merged by an SVD of the concatenated
U·Σ factors, with an a-posteriori error bound; ``rsvd`` (:535-616) is the
classic randomized range-finder.  (Iwen/Ong 2016, Himpe et al. 2018.)

Here the merge tree runs over the canonical column blocks of the global
sharded array: the "local" truncated SVDs of all blocks are computed as one
batched (vmapped) SVD on the MXU, and each merge level is a batched SVD of
concatenated U·Σ factors — log_k(p) compiled steps instead of p ranks
exchanging factors.
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...telemetry.spans import span as _span
from .. import types
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from .qr import qr

__all__ = ["hsvd", "hsvd_rank", "hsvd_rtol", "rsvd"]


def hsvd_rank(
    A: DNDarray,
    maxrank: int,
    compute_sv: bool = False,
    maxmergedim: Optional[int] = None,
    safetyshift: int = 5,
    silent: bool = True,
):
    """Hierarchical SVD with fixed truncation rank (svdtools.py:46)."""
    sanitize_in(A)
    if A.ndim != 2:
        raise ValueError(f"A must be a 2D matrix, but is {A.ndim}-dimensional")
    if not isinstance(maxrank, int) or maxrank < 1:
        raise ValueError(f"maxrank must be a positive integer, but is {maxrank}")
    return _hsvd(A, maxrank=maxrank, rtol=None, compute_sv=compute_sv, safetyshift=safetyshift, silent=silent)


def hsvd_rtol(
    A: DNDarray,
    rtol: float,
    compute_sv: bool = False,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    safetyshift: int = 5,
    no_of_merges: Optional[int] = None,
    silent: bool = True,
):
    """Hierarchical SVD with relative tolerance (svdtools.py:130)."""
    sanitize_in(A)
    if A.ndim != 2:
        raise ValueError(f"A must be a 2D matrix, but is {A.ndim}-dimensional")
    if not isinstance(rtol, float) or rtol <= 0:
        raise ValueError(f"rtol must be a positive float, but is {rtol}")
    return _hsvd(A, maxrank=maxrank, rtol=rtol, compute_sv=compute_sv, safetyshift=safetyshift, silent=silent)


def hsvd(
    A: DNDarray,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    rtol: Optional[float] = None,
    safetyshift: int = 0,
    no_of_merges: int = 2,
    compute_sv: bool = False,
    silent: bool = True,
    warnings_off: bool = False,
):
    """Generic hierarchical SVD (svdtools.py:256)."""
    sanitize_in(A)
    return _hsvd(A, maxrank=maxrank, rtol=rtol, compute_sv=compute_sv, safetyshift=safetyshift, silent=silent, no_of_merges=no_of_merges)


from functools import partial as _partial


def _hsvd_env_cfg() -> tuple:
    """The hsvd env knobs as a static jit-cache key component: toggling
    HEAT_TPU_HSVD_PRECISION / _SYRK mid-process must reach the next call
    instead of hitting a program traced under the old setting."""
    import os

    return (
        os.environ.get("HEAT_TPU_HSVD_PRECISION", ""),
        os.environ.get("HEAT_TPU_HSVD_SYRK", ""),
        os.environ.get("HEAT_TPU_HSVD_BATCHED", ""),
    )


@_partial(
    jax.jit, static_argnames=("trunc", "p", "no_of_merges", "syrk_ok", "env_cfg")
)
def _hsvd_core(dense: jnp.ndarray, trunc: int, p: int, no_of_merges: int, syrk_ok: bool = False, env_cfg: tuple = ()):
    """The whole hierarchical factorization as ONE compiled program —
    eager op-by-op dispatch of the same pipeline pays a host launch per
    op.  Returns (u_fin (m, w), s_fin (w,), v_fin
    (n, w), discarded_sq, total_sq) at full working width w; the host
    slices to the final rank (shape decisions stay outside jit)."""
    return _hsvd_body(dense, trunc, p, no_of_merges, compute_v=True, syrk_ok=syrk_ok)


@_partial(
    jax.jit,
    static_argnames=(
        "trunc", "p", "no_of_merges", "k", "compute_v", "dtype_name", "syrk_ok", "env_cfg",
    ),
)
def _hsvd_rank_jit(dense, trunc: int, p: int, no_of_merges: int, k: int, compute_v: bool, dtype_name: str, syrk_ok: bool = False, env_cfg: tuple = ()):
    """Fixed-rank hsvd INCLUDING the cast, the rank-k truncation and the
    error estimate — one device program, zero per-call eager dispatches.
    The eager version of this tail (astype + four slices + two reductions
    + re-placements) is a dozen host dispatches around one device
    program.

    The final rank ``k`` is static here, so it goes down to ``_hsvd_body``
    as the output width: the full-height products are taken at ``k``
    columns and ``U`` is written once.  Nothing below reads columns ``k``
    to ``trunc`` of ``U`` (the error estimate needs ``s`` and ``total_sq``
    only), and at the working width they cost a second pass over all ``m``
    rows to slice away.  ``u`` comes back at ``k`` columns, so
    ``hsvd.truncate`` touches nothing of ``m`` rows."""
    dense = dense.astype(jnp.dtype(dtype_name))
    u, s, v, _disc, total_sq = _hsvd_body(
        dense, trunc, p, no_of_merges, compute_v, syrk_ok, out_width=k
    )
    with jax.named_scope("hsvd.truncate"):
        sv = s[:k]
        approx_sq = jnp.sum(sv.astype(jnp.float32) ** 2)
        rel_err = jnp.sqrt(
            jnp.maximum(total_sq - approx_sq, 0.0) / jnp.maximum(total_sq, 1e-30)
        )
        if compute_v:
            return u, sv, v[:, :k], rel_err
        return u, sv, rel_err


def _hsvd_body(dense: jnp.ndarray, trunc: int, p: int, no_of_merges: int, compute_v: bool, syrk_ok: bool = False, out_width: Optional[int] = None):
    """(u_fin, s_fin, v_fin, discarded_sq, total_sq).  ``out_width`` is the
    static number of columns the caller will keep of the full-height
    results (``u_fin``; in the merge tree ``v_fin`` too); ``None`` keeps
    the working width, for the rtol path, whose rank is a host decision
    made after the program returns.  ``s_fin`` always has the working
    width."""
    m, n = dense.shape

    # leaf level: column blocks = the canonical shards of the split axis
    # (split=1 in the reference's flagship use; any split or none works)
    if p > 1 and n >= p:
        block_cols = [dense[:, s.start : s.stop] for s in _col_slices(n, p)]
    else:
        block_cols = [dense]

    if len(block_cols) == 1 and m >= n:
        # single-leaf tall case (the per-chip flagship): one Gram pass
        # gives EVERYTHING — eigh(G) = (sigma^2, right singular vectors),
        # us = A @ V_kk already has orthogonal columns with norms sigma_i,
        # so the generic path's final re-factorization (a second eigh) is
        # identity work and its V = A^T u / s pass re-reads A for what is
        # exactly V_kk.  Two reads of A instead of three and one eigh
        # instead of two: the r4 profile showed this config bandwidth-
        # bound on those reads (VERDICT r4 #4).  The Gram itself goes
        # through the Pallas syrk kernel where supported — XLA's generic
        # dot streams x twice (lhs x.T + rhs x), the kernel reads each row
        # tile once, at the memory's own rate (8.51 ms for the cell's
        # 6.44 GB, chip run, PR 30).  The
        # kernel path needs a SINGLE-DEVICE operand (pallas_call is not
        # GSPMD-partitionable), so the caller gates ``syrk_ok`` on the
        # communication layout outside the jit.
        # The projection is taken at ``ko`` columns, the rank path's final
        # rank: sliced after, the extra trunc - k columns cost a second
        # pass over all m rows of U.  Multiplying by 1/s AFTER the product
        # keeps every kept column's arithmetic (scaling V first differs in
        # the last bit), and XLA fuses the multiply into the product's
        # output.  s, disc, total_sq and V come from the n x n eigh at the
        # working width as before: they are tiny.
        # The scopes are names for the device trace (op_name metadata);
        # they change no operation of the compiled program.
        with jax.named_scope("hsvd.gram"):
            g = _gram(dense, syrk_ok)
        with jax.named_scope("hsvd.eigh"):
            lam, v = jnp.linalg.eigh(g)
            lam = lam[::-1]
            v = v[:, ::-1]
            kk = min(trunc, n)
            disc = jnp.sum(jnp.maximum(lam[kk:].astype(jnp.float32), 0.0))
            total_sq = jnp.sum(jnp.maximum(lam.astype(jnp.float32), 0.0))
            lam_k = jnp.maximum(lam[:kk], 0.0)
            eps = float(jnp.finfo(dense.dtype).eps)
            keep = lam_k > eps * jnp.maximum(lam_k[0], 1e-30)
            s_fin = jnp.where(keep, jnp.sqrt(lam_k), 0.0)
            inv_s = jnp.where(keep, 1.0 / jnp.maximum(jnp.sqrt(lam_k), 1e-30), 0.0)
        ko = kk if out_width is None else min(out_width, kk)
        with jax.named_scope("hsvd.project"):
            u_fin = (
                jnp.matmul(dense, v[:, :ko], precision=jax.lax.Precision.HIGHEST)
                * inv_s[None, :ko]
            )
        v_fin = v[:, :kk] if compute_v else None
        return u_fin, s_fin, v_fin, disc, total_sq

    # leaf truncated SVDs; track the energy each truncation discards so the
    # rtol bound covers leaf+merge losses (reference's a-posteriori bound,
    # svdtools.py:430).  ||A||_F^2 falls out of the leaf Gram traces for
    # free — a separate full-array sum-of-squares pass would re-read the
    # whole matrix from HBM (measurably as costly as one Gram matmul).
    # HEAT_TPU_HSVD_BATCHED=1: equal-shape tall blocks of a level run as
    # ONE stacked gram + batched eigh + batched matmul instead of the
    # sequential per-block loop — the A/B for the "eigh can't fuse"
    # claim the merge-tree floor rests on.  Trace-time env read; the
    # env_cfg static arg keys the jit cache so a toggle retraces.
    from .._env import env_flag as _env_flag

    batched = _env_flag("HEAT_TPU_HSVD_BATCHED")

    def _level(blocks):
        if (
            batched
            and len(blocks) > 1
            and len({b.shape for b in blocks}) == 1
            and blocks[0].shape[0] >= blocks[0].shape[1]
        ):
            us_s, disc, sq = _truncated_us_stacked(jnp.stack(blocks), trunc)
            return list(us_s), disc, sq
        outs, disc, sq = [], jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
        for blk in blocks:
            us_f, d, b_sq = _truncated_us(blk, trunc)
            disc = disc + d
            sq = sq + b_sq
            outs.append(us_f)
        return outs, disc, sq

    factors: List[jnp.ndarray]
    factors, discarded_sq, total_sq = _level(block_cols)

    # merge tree (levels of no_of_merges-way merges, svdtools.py:330+)
    while len(factors) > 1:
        cats = [
            jnp.concatenate(factors[i : i + no_of_merges], axis=1)
            for i in range(0, len(factors), no_of_merges)
        ]
        factors, disc, _ = _level(cats)
        discarded_sq = discarded_sq + disc

    # The leaves and merges above keep ``trunc``: the safety shift is what
    # makes the merged subspace accurate.  Only the final factorization's
    # full-height products narrow to the caller's ``out_width``.
    us = factors[0]
    ko = us.shape[1] if out_width is None else min(out_width, us.shape[1])
    if us.shape[0] >= us.shape[1]:
        # final factorization through the Gram matrix as well — us is
        # (m, <= trunc), so eigh is tiny and the two matmuls ride the MXU
        g_fin = jnp.matmul(us.T, us, precision=jax.lax.Precision.HIGHEST)
        lam_fin, v_eig = jnp.linalg.eigh(g_fin)
        lam_fin = jnp.maximum(lam_fin[::-1], 0.0)
        v_eig = v_eig[:, ::-1]
        # eigenvalues below the Gram noise floor (~eps relative, i.e.
        # sigma < ~sqrt(eps) * sigma_1) are numerical noise whose "singular
        # vectors" live inside the dominant column space — keeping them
        # double-counts energy; drop value and column together.  The floor
        # scales with the working dtype (f32: ~1.2e-7, f64: ~2.2e-16).
        eps = float(jnp.finfo(us.dtype).eps)
        keep = lam_fin > eps * jnp.maximum(lam_fin[0], 1e-30)
        s_fin = jnp.where(keep, jnp.sqrt(lam_fin), 0.0)
        inv_s = jnp.where(keep, 1.0 / jnp.maximum(jnp.sqrt(lam_fin), 1e-30), 0.0)
        u_fin = (
            jnp.matmul(us, v_eig[:, :ko], precision=jax.lax.Precision.HIGHEST)
            * inv_s[None, :ko]
        )
    else:
        u_fin, s_fin, _ = jnp.linalg.svd(us, full_matrices=False)
        u_fin = u_fin[:, :ko]

    # V = A^T U diag(1/s) at U's width; skipped entirely when the caller
    # doesn't want V — it is a second full-size MXU matmul
    if compute_v:
        inv_sv = jnp.where(s_fin > 0, 1.0 / jnp.maximum(s_fin, 1e-30), 0.0)
        v_fin = (
            jnp.matmul(dense.T, u_fin, precision=jax.lax.Precision.HIGHEST)
            * inv_sv[None, :ko]
        )
    else:
        v_fin = None
    return u_fin, s_fin, v_fin, discarded_sq, total_sq


def _hsvd(
    A: DNDarray,
    maxrank: Optional[int],
    rtol: Optional[float],
    compute_sv: bool,
    safetyshift: int,
    silent: bool,
    no_of_merges: int = 2,
):
    m, n = A.shape
    attrs = {"rank": maxrank} if rtol is None else {"rtol": rtol}
    # Host spans at the layer boundaries: the root is the API layer,
    # ``hsvd.dispatch`` the jit cache lookup and enqueue (trace and compile
    # on a miss), ``hsvd.wrap`` the results' re-placement.  What the root
    # holds beside them is this function's own host work.
    with _span("ht.linalg.hsvd", rows=m, cols=n, split=A.split, **attrs):
        comm = A.comm
        dtype = jnp.float32 if not types.heat_type_is_inexact(A.dtype) else A.dtype.jax_type()

        if maxrank is None:
            maxrank = min(m, n)
        trunc = min(maxrank + safetyshift, m)
        p = comm.size if A.split == 1 else 1
        dense = A._dense()
        env_cfg = _hsvd_env_cfg()
        u_split = A.split if A.split == 0 else None
        v_split = A.split if A.split == 1 else None

        if rtol is None:
            # fixed-rank fast path: cast, factorization, truncation and the
            # error estimate are ONE device program — no eager dispatches
            # around the factorization
            k = min(maxrank, trunc)
            with _span("hsvd.dispatch", path="rank"):
                outs = _hsvd_rank_jit(
                    dense, trunc, p, no_of_merges, k, compute_sv, str(jnp.dtype(dtype)),
                    syrk_ok=comm.size == 1, env_cfg=env_cfg,
                )
            with _span("hsvd.wrap"):
                U = DNDarray.from_dense(outs[0], u_split, A.device, comm)
                if not compute_sv:
                    return U, outs[2]
                S = DNDarray.from_dense(outs[1], None, A.device, comm)
                V = DNDarray.from_dense(outs[2], v_split, A.device, comm)
            return U, S, V, outs[3]

        dense = dense.astype(dtype)
        with _span("hsvd.dispatch", path="rtol"):
            u_fin, s_fin, v_fin, discarded_sq, total_sq = _hsvd_core(
                dense, trunc, p, no_of_merges, syrk_ok=comm.size == 1, env_cfg=env_cfg,
            )

        # rtol path: smallest k with (energy discarded by leaf/merge
        # truncations + energy of the dropped tail of s_fin) <= rtol^2 *
        # ||A||_F^2 — k is a host shape decision, so this path syncs once
        kept = jnp.cumsum(s_fin.astype(jnp.float32) ** 2)
        resid = jnp.sum(s_fin.astype(jnp.float32) ** 2) - kept + discarded_sq
        ok = np.asarray(resid <= (rtol**2) * total_sq)
        k = int(np.argmax(ok)) + 1 if ok.any() else int(s_fin.shape[0])
        k = min(k, maxrank)
        u_k = u_fin[:, :k]
        sv = s_fin[:k]

        # relative error estimate ||A - U U^T A||_F / ||A||_F (svdtools.py:430+)
        approx_sq = jnp.sum(sv**2)
        rel_err = jnp.sqrt(jnp.maximum(total_sq - approx_sq, 0.0) / jnp.maximum(total_sq, 1e-30))

        # the error estimate stays a lazy 0-d jax scalar: float()-ing it here
        # would force a device->host round trip inside every hsvd call;
        # callers convert on use
        v_k = v_fin[:, :k] if compute_sv else None
        with _span("hsvd.wrap"):
            U = DNDarray.from_dense(u_k, u_split, A.device, comm)
            if not compute_sv:
                return U, rel_err
            S = DNDarray.from_dense(sv, None, A.device, comm)
            V = DNDarray.from_dense(v_k, v_split, A.device, comm)
        return U, S, V, rel_err


def _gram(blk: jnp.ndarray, syrk_ok: bool = False) -> jnp.ndarray:
    """``blk.T @ blk`` through the one-read syrk kernel when supported
    (f32, lane-aligned width, single-device operand — ``syrk_ok`` is the
    caller's static layout gate), else an XLA dot at the hsvd Gram
    precision (see ``_gram_precision``).  Disable with
    HEAT_TPU_HSVD_SYRK=0."""
    import os

    from ..kernels import gram_syrk, syrk_supported

    m, n = blk.shape
    prec = _gram_precision()
    if (
        syrk_ok
        and prec is not jax.lax.Precision.HIGHEST  # 'highest' forces f32 dots
        and os.environ.get("HEAT_TPU_HSVD_SYRK", "1") == "1"
        and syrk_supported(m, n, blk.dtype)
    ):
        return gram_syrk(blk)
    return jnp.matmul(blk.T, blk, precision=prec)


def _gram_precision():
    """Matmul precision for hsvd's Gram passes.

    Default HIGH = compensated bf16x3 (each f32 operand split into hi+lo
    bfloat16; three terms, which XLA takes as three MXU passes and
    ``kernels.gram_syrk`` as two, the third being the second's transpose)
    — ~3e-6 relative error on G (the dropped lo^T lo reads the diagonal
    that much low), half the MXU time of the 6-pass HIGHEST policy, and the
    hsvd truncation error dominates it by orders of magnitude for any
    rank-truncated use (VERDICT r4 #4's sanctioned bf16-accumulate move).
    Every non-Gram matmul in the pipeline stays HIGHEST; set
    HEAT_TPU_HSVD_PRECISION=highest to force full f32 throughout."""
    from .._env import precision_from_env

    return precision_from_env("HEAT_TPU_HSVD_PRECISION", "high")


def _gram_orthonormalize(y: jnp.ndarray, passes: int = 2) -> jnp.ndarray:
    """Orthonormal basis of a tall matrix via symmetric (Loewdin) Gram
    orthogonalization: Q = y V diag(lam^-1/2) V^T with (lam, V) = eigh(y^T y).

    Two passes (the CholeskyQR2 recipe) push orthogonality error to
    ~machine eps for the moderately conditioned matrices rsvd produces,
    and everything is MXU matmuls + a tiny eigh — ~10x faster than
    Householder QR on v5e for tall-skinny shapes.
    """
    q = y
    for _ in range(passes):
        g = jnp.matmul(q.T, q, precision=jax.lax.Precision.HIGHEST)
        lam, v = jnp.linalg.eigh(g)
        # directions below the Gram noise floor (rank-deficient input)
        # are dropped, not noise-amplified: their columns become zero and a
        # downstream SVD sorts them to the tail (floor scales with dtype)
        cutoff = float(jnp.finfo(q.dtype).eps) * jnp.maximum(jnp.max(lam), 1e-30)
        inv_sqrt = jnp.where(lam > cutoff, 1.0 / jnp.sqrt(jnp.maximum(lam, 1e-30)), 0.0)
        w = jnp.matmul(v * inv_sqrt[None, :], v.T, precision=jax.lax.Precision.HIGHEST)
        q = jnp.matmul(q, w, precision=jax.lax.Precision.HIGHEST)
    return q


def _truncated_us(blk: jnp.ndarray, trunc: int):
    """Truncated ``U * s`` factor of a block + the discarded squared energy.

    Tall blocks (rows >= cols — every leaf and merge block of the flagship
    tall-skinny workload) go through the Gram matrix: ``G = blk.T @ blk``
    is one MXU matmul, its (cols, cols) eigh is trivial, and
    ``U*s = blk @ V`` is a second matmul — the whole factorization runs at
    matmul speed instead of Householder-SVD speed (~10x on v5e).  The
    squared-singular-value spectrum comes out of eigh directly, so the
    a-posteriori rtol bound is unchanged.  Gram squares the condition
    number, which for a *truncated* factor only perturbs directions with
    sigma below ~sqrt(eps)*sigma_1 — those are exactly the ones the
    truncation bound already charges to the error budget.  Wide blocks
    fall back to Householder SVD.
    """
    m, n = blk.shape
    if m >= n:
        g = jnp.matmul(blk.T, blk, precision=_gram_precision())
        lam, v = jnp.linalg.eigh(g)  # ascending
        lam = lam[::-1]
        v = v[:, ::-1]
        kk = min(trunc, n)
        disc = jnp.sum(jnp.maximum(lam[kk:].astype(jnp.float32), 0.0))
        blk_sq = jnp.sum(jnp.maximum(lam.astype(jnp.float32), 0.0))  # tr(G) = ||blk||_F^2
        us = jnp.matmul(blk, v[:, :kk], precision=jax.lax.Precision.HIGHEST)
        return us, disc, blk_sq
    u_full, s_full, _ = jnp.linalg.svd(blk, full_matrices=False)
    kk = min(trunc, s_full.shape[0])
    disc = jnp.sum(s_full[kk:].astype(jnp.float32) ** 2)
    blk_sq = jnp.sum(s_full.astype(jnp.float32) ** 2)
    return u_full[:, :kk] * s_full[:kk][None, :], disc, blk_sq


def _truncated_us_stacked(blocks: jnp.ndarray, trunc: int):
    """Batched ``_truncated_us`` over equal-shape TALL blocks: blocks is
    (b, m, n) with m >= n; one batched Gram matmul, one batched eigh and
    one batched projection replace b sequential rounds.  Numerically
    identical per block (eigh batches matrix-wise); returns the stacked
    ``U*s`` factors plus the level's pooled discarded/total energies."""
    _b, _m, n = (int(s) for s in blocks.shape)
    g = jnp.matmul(
        jnp.swapaxes(blocks, 1, 2), blocks, precision=_gram_precision()
    )
    lam, v = jnp.linalg.eigh(g)  # ascending, batched
    lam = lam[:, ::-1]
    v = v[:, :, ::-1]
    kk = min(trunc, n)
    disc = jnp.sum(jnp.maximum(lam[:, kk:].astype(jnp.float32), 0.0))
    blk_sq = jnp.sum(jnp.maximum(lam.astype(jnp.float32), 0.0))
    us = jnp.matmul(blocks, v[:, :, :kk], precision=jax.lax.Precision.HIGHEST)
    return us, disc, blk_sq


def _col_slices(n: int, p: int):
    per = -(-n // p)
    out = []
    start = 0
    while start < n:
        stop = min(start + per, n)
        out.append(slice(start, stop))
        start = stop
    return out


def rsvd(
    A: DNDarray,
    rank: int,
    n_oversamples: int = 10,
    power_iter: int = 0,
    qr_procs_to_merge: int = 2,
):
    """Randomized SVD (svdtools.py:535): Gaussian range sampling, optional
    power iteration, QR, small SVD."""
    sanitize_in(A)
    if not isinstance(rank, int) or rank < 1:
        raise ValueError(f"rank must be a positive integer, but is {rank}")
    if not isinstance(n_oversamples, int) or n_oversamples < 0:
        raise ValueError(f"n_oversamples must be a non-negative integer, but is {n_oversamples}")
    if not isinstance(power_iter, int) or power_iter < 0:
        raise ValueError(f"power_iter must be a non-negative integer, but is {power_iter}")
    from .. import random as ht_random

    m, n = A.shape
    ell = min(rank + n_oversamples, m, n)
    dtype = jnp.float32 if not types.heat_type_is_inexact(A.dtype) else A.dtype.jax_type()
    omega = ht_random.randn(n, ell, dtype=types.canonical_heat_type(dtype), comm=A.comm)._dense()
    k = min(rank, min(ell, m))
    u_k, s_k, vt_k = _centered_rsvd(A._dense(), jnp.zeros((n,), dtype), omega, power_iter, k, with_u=True)
    U = DNDarray.from_dense(u_k, A.split if A.split == 0 else None, A.device, A.comm)
    S = DNDarray.from_dense(s_k, None, A.device, A.comm)
    V = DNDarray.from_dense(vt_k.T, None, A.device, A.comm)
    return U, S, V


@_partial(jax.jit, static_argnames=("power_iter", "k", "with_u"))
def _centered_rsvd(x, shift, omega, power_iter: int, k: int, real=None, with_u: bool = False):
    """``(U or None, s, vt)``, the rank-``k`` randomized factorization of
    ``x - shift`` as ONE device program (range sampling, power iterations,
    small SVD); ``x`` and ``omega`` are taken in ``shift``'s dtype.

    Each product takes ``x - shift`` (times ``real``, 1 on the rows that
    count, where the caller hands it) as it reads ``x``: the compiler puts
    the subtraction into the product's own fusion, so the centring costs
    neither a read nor a write of the table, and the products see the
    columns' spreads and not their means (``rsvd`` hands a zero shift).
    Every product is at ``highest`` and every sketch is orthonormalized by
    :func:`_gram_orthonormalize`, in ONE loop of ``power_iter + 1`` turns: a
    turn reads the table twice, ``Y = Xc Q_f`` (``Q_f = omega`` on the first
    turn) and ``B = Q_n^T Xc`` with ``Q_n`` the basis of ``Y``, and on every
    turn but the last orthonormalizes ``B^T`` into the next ``Q_f``.  The
    last turn's ``B``'s small SVD gives ``s`` and ``vt``, and with
    ``with_u`` ``U = Q_n u_B``, which a caller such as PCA that reads no
    ``U`` neither computes nor writes: ``2 power_iter + 2`` reads of the
    table in all, and one loop body whose operations the device trace names
    once however many turns it runs.  ``B`` is written ``ell``-first, as
    ``Y`` is: the trace tells the products from the sketch's own passes,
    which write ``rows x ell``, by their shapes."""
    hi = jax.lax.Precision.HIGHEST
    x, omega = x.astype(shift.dtype), omega.astype(shift.dtype)

    def centred():
        xc = x - shift[None, :]
        return xc if real is None else xc * real[:, None]

    def turn(i, carry):
        q_f, _, _ = carry
        q_n = _gram_orthonormalize(jnp.matmul(q_f.T, centred().T, precision=hi).T)
        b = jnp.matmul(q_n.T, centred(), precision=hi)
        q_f = jax.lax.cond(i < power_iter, _gram_orthonormalize, lambda z: z, b.T)
        return q_f, b, q_n if with_u else None

    q_n = jnp.zeros((x.shape[0], omega.shape[1]), x.dtype) if with_u else None
    _, b, q_n = jax.lax.fori_loop(0, power_iter + 1, turn, (omega, jnp.zeros_like(omega.T), q_n))
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = jnp.matmul(q_n, u_b[:, :k], precision=hi) if with_u else None
    return u, s[:k], vt[:k]
