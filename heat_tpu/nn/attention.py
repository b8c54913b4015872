"""Sequence-parallel attention: ring attention and all-to-all (Ulysses).

The reference has no attention (SURVEY.md §2: TP/PP/CP "ABSENT in the
reference" — heat is not an LLM framework), but its long-dimension
primitives — halo exchange (dndarray.py:387), the spatial ring
(distance.py:209), and pencil resplit (fft.py:100-137) — are exactly the
communication patterns context parallelism needs.  This module closes that
loop: the same ``shard_map`` + ``ppermute`` / ``all_to_all`` machinery the
rest of the framework uses, applied to scaled-dot-product attention so
sequences longer than one chip's HBM are first-class.

Two strategies, both exact (not approximations):

* **ring**: every device holds one sequence block of Q, K, V; K/V blocks
  rotate around the ICI ring (one ``ppermute`` per step, overlapped with
  the block matmuls by XLA) while a numerically-stable online softmax
  (flash-attention accumulation) folds each visiting block into the
  output.  Memory per device is O(seq/p); the full (seq x seq) score
  matrix never materializes.
* **ulysses** (all-to-all): one ``all_to_all`` re-shards from
  sequence-split to head-split, each device runs full-sequence attention
  on its heads, and a second ``all_to_all`` restores sequence sharding.
  Requires ``heads % p == 0``; cheaper for moderate sequences, two
  collectives total.
"""

from __future__ import annotations

import functools
import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..core.dndarray import DNDarray
from ..parallel.comm import Communication, sanitize_comm

__all__ = ["scaled_dot_product_attention", "ring_attention", "ulysses_attention"]

_NEG_INF = -1e30


def _flash_available() -> bool:
    """Whether the TPU Pallas flash-attention kernel can be used.

    The kernel's win on TPU is MEMORY, not raw speed: the (h, seq, seq)
    score tensor never materializes, so full-sequence local attention
    scales to lengths where the einsum path OOMs.  Opt out with
    HEAT_TPU_FLASH=0."""
    return os.environ.get("HEAT_TPU_FLASH", "1") == "1" and jax.default_backend() == "tpu"


def _local_flash(q, k, v, scale, causal, n_true):
    """Full-sequence attention via the Pallas flash kernel.

    ``q``/``k``/``v`` are (seq, heads, head_dim); padded tail positions
    (>= n_true) are isolated with segment ids so real tokens never attend
    padding.  Raises at trace time when the kernel rejects the shape —
    callers pass that on: whoever asked for flash gets the kernel or the
    error, never a silent einsum."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention,
    )

    s = q.shape[0]
    qb = q.transpose(1, 0, 2)[None].astype(jnp.float32)  # (1, h, s, d)
    kb = k.transpose(1, 0, 2)[None].astype(jnp.float32)
    vb = v.transpose(1, 0, 2)[None].astype(jnp.float32)
    seg = None
    if n_true < s:
        ids = (jnp.arange(s) >= n_true).astype(jnp.int32)[None]
        seg = SegmentIds(q=ids, kv=ids)
    out = flash_attention(qb, kb, vb, causal=causal, sm_scale=scale, segment_ids=seg)
    return out[0].transpose(1, 0, 2).astype(q.dtype)


def _block_attn_update(o, m, l, q, k, v, q_off, k_off, scale, causal, n_true):
    """Fold one K/V block into the running (output, max, denom) triple.

    Flash-attention online softmax: scores are computed in f32, the running
    max ``m`` and denominator ``l`` are rescaled as new blocks arrive.
    ``q_off``/``k_off`` are the global positions of the local blocks —
    needed for causal masking and for masking the padded tail rows
    (global index >= n_true) the pad-and-mask invariant introduces.
    """
    sq, h, d = q.shape
    sk = k.shape[0]
    scores = (
        jnp.einsum(
            "qhd,khd->hqk", q, k,
            preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
        )
        * scale
    )
    k_pos = k_off + jnp.arange(sk)
    mask = (k_pos < n_true)[None, None, :]
    if causal:
        q_pos = q_off + jnp.arange(sq)
        mask = mask & (k_pos[None, None, :] <= q_pos[None, :, None])
    scores = jnp.where(mask, scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))  # (h, sq)
    corr = jnp.exp(m - m_new)
    p_block = jnp.exp(scores - m_new[..., None])  # (h, sq, sk)
    # rows whose every key so far is masked have m_new == -inf and
    # exp(scores - m_new) == exp(0): zero those weights explicitly so a
    # fully-masked block contributes nothing regardless of arrival order
    p_block = jnp.where(mask, p_block, 0.0)
    l_new = l * corr + p_block.sum(axis=-1)
    pv = jnp.einsum(
        "hqk,khd->qhd", p_block, v.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    o_new = o * corr.T[..., None] + pv
    return o_new, m_new, l_new


def _ring_body(q, k, v, *, comm: Communication, scale, causal, n_true, block):
    """shard_map body: one sequence block of q/k/v per device."""
    p = comm.size
    name = comm.axis_name
    idx = jax.lax.axis_index(name)
    sq, h, d = q.shape
    qf = q.astype(jnp.float32)
    o = jnp.zeros((sq, h, d), jnp.float32)
    m = jnp.full((h, sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((h, sq), jnp.float32)
    q_off = idx * block
    for step in range(p):
        src = (idx - step) % p  # owner of the K/V block currently held
        o, m, l = _block_attn_update(
            o, m, l, qf, k, v, q_off, src * block, scale, causal, n_true
        )
        if step != p - 1:
            perm = [(i, (i + 1) % p) for i in range(p)]
            k = jax.lax.ppermute(k, name, perm)
            v = jax.lax.ppermute(v, name, perm)
    return (o / jnp.maximum(l, 1e-30).T[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    comm: Optional[Communication] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    n_true: Optional[int] = None,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded around the ICI ring.

    ``q``/``k``/``v`` are global arrays of shape (seq, heads, head_dim)
    whose leading axis length is a multiple of ``comm.size`` (the
    pad-and-mask layer guarantees this for DNDarray inputs; raw callers
    pass padded arrays plus ``n_true``).
    """
    comm = sanitize_comm(comm)
    seq, h, d = q.shape
    if seq % comm.size:
        raise ValueError(f"padded sequence {seq} must divide the mesh size {comm.size}")
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    n_true = seq if n_true is None else n_true
    block = seq // comm.size
    return _ring_fn(comm, float(scale), bool(causal), int(n_true), block)(q, k, v)


@functools.lru_cache(maxsize=128)
def _ring_fn(comm, scale, causal, n_true, block):
    """Jitted, cached ring-attention executable — rebuilding the shard_map
    per call would retrace and recompile every time."""
    body = partial(
        _ring_body, comm=comm, scale=scale, causal=causal, n_true=n_true, block=block
    )
    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=(P(comm.axis_name), P(comm.axis_name), P(comm.axis_name)),
            out_specs=P(comm.axis_name),
        )
    )


def _ulysses_body(q, k, v, *, comm, scale, causal, n_true, use_flash):
    """shard_map body: all_to_all seq->heads, local attention, reverse."""
    name = comm.axis_name
    # (block, h, d) -> (seq, h/p, d): gather sequence, scatter heads
    qg = jax.lax.all_to_all(q, name, split_axis=1, concat_axis=0, tiled=True)
    kg = jax.lax.all_to_all(k, name, split_axis=1, concat_axis=0, tiled=True)
    vg = jax.lax.all_to_all(v, name, split_axis=1, concat_axis=0, tiled=True)
    seq = qg.shape[0]
    if use_flash:
        # each device now holds the FULL sequence for h/p heads — the
        # shape flash attention wants; the (h/p, seq, seq) score tensor
        # of the einsum path never materializes
        og = _local_flash(qg, kg, vg, scale, causal, n_true)
    else:
        scores = (
            jnp.einsum(
                "qhd,khd->hqk", qg.astype(jnp.float32), kg,
                preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            )
            * scale
        )
        k_pos = jnp.arange(seq)
        mask = (k_pos < n_true)[None, None, :]
        if causal:
            mask = mask & (k_pos[None, None, :] <= k_pos[None, :, None])
        scores = jnp.where(mask, scores, _NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        og = jnp.einsum(
            "hqk,khd->qhd", weights, vg.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
        ).astype(q.dtype)
    # (seq, h/p, d) -> (block, h, d)
    return jax.lax.all_to_all(og, name, split_axis=0, concat_axis=1, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    comm: Optional[Communication] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    n_true: Optional[int] = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Exact attention via all-to-all sequence parallelism (Ulysses style).

    ``use_flash=True`` runs the local full-sequence attention through the
    Pallas flash kernel (TPU only): the (h/p, seq, seq) score tensor never
    materializes, trading the einsum path's HIGHEST-precision matmuls for
    the kernel's default MXU precision (~1e-2 f32 outputs).
    """
    comm = sanitize_comm(comm)
    seq, h, d = q.shape
    if seq % comm.size:
        raise ValueError(f"padded sequence {seq} must divide the mesh size {comm.size}")
    if h % comm.size:
        raise ValueError(f"ulysses needs heads ({h}) divisible by the mesh size ({comm.size})")
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    n_true = seq if n_true is None else n_true
    flash = bool(use_flash) and _flash_available()
    return _ulysses_fn(comm, float(scale), bool(causal), int(n_true), flash)(q, k, v)


@functools.lru_cache(maxsize=128)
def _ulysses_fn(comm, scale, causal, n_true, use_flash=False):
    """Jitted, cached Ulysses executable (see _ring_fn)."""
    body = partial(
        _ulysses_body, comm=comm, scale=scale, causal=causal, n_true=n_true,
        use_flash=use_flash,
    )
    return jax.jit(
        _shard_map(
            body,
            mesh=comm.mesh,
            in_specs=(P(comm.axis_name), P(comm.axis_name), P(comm.axis_name)),
            out_specs=P(comm.axis_name),
        )
    )


def scaled_dot_product_attention(
    q: DNDarray,
    k: DNDarray,
    v: DNDarray,
    causal: bool = False,
    scale: Optional[float] = None,
    method: str = "ring",
) -> DNDarray:
    """DNDarray-level exact attention over the sequence-split axis.

    Inputs are (seq, heads, head_dim) DNDarrays, all with the same split:
    ``split=0`` runs the distributed strategy chosen by ``method``
    ("ring", "ulysses", or its alias "alltoall"); ``split=None`` computes
    locally.
    """
    for name, t in (("q", q), ("k", k), ("v", v)):
        if not isinstance(t, DNDarray):
            raise TypeError(f"{name} must be a DNDarray, got {type(t)}")
        if t.ndim != 3:
            raise ValueError(f"{name} must be (seq, heads, head_dim), got {t.ndim}-D")
    if not (q.split == k.split == v.split):
        raise ValueError(f"q/k/v must share a split, got {q.split}/{k.split}/{v.split}")
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError("q/k/v must have identical shapes (self-attention blocks)")

    seq, h, d = q.shape
    scale = 1.0 / math.sqrt(d) if scale is None else scale

    if method not in ("ring", "ulysses", "alltoall", "flash"):
        raise ValueError(
            f'method must be "ring", "ulysses", "alltoall" or "flash", got {method!r}'
        )

    if q.split is None:
        qd, kd, vd = q._dense(), k._dense(), v._dense()
        if method == "flash" and _flash_available():
            # memory-bounded local kernel (opt-in): scales past the einsum
            # path's (h, seq, seq) materialization limit at the cost of
            # the kernel's default MXU precision
            out = _local_flash(qd, kd, vd, scale, causal, seq)
            return DNDarray.from_dense(out, None, q.device, q.comm)
        scores = (
            jnp.einsum(
                "qhd,khd->hqk", qd.astype(jnp.float32), kd,
                precision=jax.lax.Precision.HIGHEST,
            )
            * scale
        )
        if causal:
            pos = jnp.arange(seq)
            scores = jnp.where(pos[None, None, :] <= pos[None, :, None], scores, _NEG_INF)
        out = jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, -1), vd.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return DNDarray.from_dense(out.astype(qd.dtype), None, q.device, q.comm)
    if q.split != 0:
        raise ValueError(f"attention is sequence-parallel over split=0, got split={q.split}")

    # "flash" on a split sequence = Ulysses re-sharding with the flash
    # local kernel (each device gets the full sequence for its heads)
    if method == "ring":
        out_padded = ring_attention(
            q.larray_padded, k.larray_padded, v.larray_padded,
            comm=q.comm, causal=causal, scale=scale, n_true=seq,
        )
    else:
        out_padded = ulysses_attention(
            q.larray_padded, k.larray_padded, v.larray_padded,
            comm=q.comm, causal=causal, scale=scale, n_true=seq,
            use_flash=(method == "flash"),
        )
    sliced = out_padded[:seq]
    return DNDarray.from_dense(sliced, 0, q.device, q.comm)
