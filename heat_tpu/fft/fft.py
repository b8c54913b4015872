"""Distributed FFT, analog of heat/fft/fft.py (22 exports).

The reference implements pencil-decomposition FFT by hand: a transform
along the split axis transposes that axis to 0, resplits to 1 (an MPI
Alltoallw with subarray datatypes), runs the local torch FFT, and resplits
back (``__fft_op`` fft.py:40-138, ``__fftn_op`` :139-298).  Under GSPMD a
single ``jnp.fft.*`` call over the sharded global array compiles to exactly
that pencil schedule (transpose-based distributed FFT with all-to-alls on
the mesh) — SURVEY.md §3.6.  What remains here is axis/split bookkeeping
and the real-transform Nyquist length arithmetic.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..core import types
from ..core.dndarray import DNDarray
from ..core.stride_tricks import sanitize_axis

__all__ = [
    "fft",
    "fft2",
    "fftfreq",
    "fftn",
    "fftshift",
    "hfft",
    "hfft2",
    "hfftn",
    "ifft",
    "ifft2",
    "ifftn",
    "ifftshift",
    "ihfft",
    "ihfft2",
    "ihfftn",
    "irfft",
    "irfft2",
    "irfftn",
    "rfft",
    "rfft2",
    "rfftfreq",
    "rfftn",
]


def _wrap(x: DNDarray, result, out_split_hint: Optional[int] = "same"):
    split = x.split if out_split_hint == "same" else out_split_hint
    if split is not None and split >= result.ndim:
        split = None
    return DNDarray.from_dense(result, split, x.device, x.comm)


def _check(x):
    if not isinstance(x, DNDarray):
        raise TypeError(f"x must be a DNDarray, is {type(x)}")


def _complex_dense(x: DNDarray):
    dense = x._dense()
    if types.heat_type_is_exact(x.dtype):
        dense = dense.astype(jnp.float32)
    return dense


# ----------------------------------------------------------------------
# planar (real-pair) execution: the transform runs as real matmuls on
# (re, im) planes — the engine with the leading-contraction path and its
# Pallas stage kernels.  Every op below routes through ``_planar_entry``
# when ``HEAT_TPU_PLANAR=1`` (read per call); the complex result is a
# planar-backed DNDarray (two real planes on the mesh) that materializes
# to a complex array on the mesh only if a non-planar-aware op touches
# it.  Unset, transforms take ``jnp.fft`` on native complex.
# ----------------------------------------------------------------------
import functools as _functools
import os as _os

from . import _planar as _pl


def _use_planar() -> bool:
    from ..core._env import env_flag

    return env_flag("HEAT_TPU_PLANAR")


def _promote_plane(buf):
    """Promote a plane to at least float32 — jnp.fft promotes f16/bf16 to
    complex64, so half-precision planes would both lose ~1e-3 accuracy in
    the DFT matmuls and break jax.lax.complex materialization."""
    if not jnp.issubdtype(buf.dtype, jnp.floating) or buf.dtype.itemsize < 4:
        return buf.astype(jnp.float32)
    return buf


def _planes_in(x: DNDarray):
    """True-shape (re, im|None) planes of ``x`` on the compute mesh."""
    if x._planar is not None:
        re, im = x._planar
        if x._pad:
            sl = tuple(
                slice(0, x.shape[d]) if d == x.split else slice(None)
                for d in range(x.ndim)
            )
            re, im = re[sl], im[sl]
        return re, im
    if types.heat_type_is_complexfloating(x.dtype):
        # native complex input: split into planes.  device_put needs
        # divisible extents, so pad to canonical first and slice the
        # pad back off on-mesh.
        dense = x._dense()
        re, im = jnp.real(dense), jnp.imag(dense)
        re = _repad(re, x.shape, x.split, x.comm)
        im = _repad(im, x.shape, x.split, x.comm)
        if x.split is not None and re.shape[x.split] != x.shape[x.split]:
            sl = tuple(
                slice(0, x.shape[d]) if d == x.split else slice(None)
                for d in range(x.ndim)
            )
            re, im = re[sl], im[sl]
        return re, im
    dense = x._dense()
    dense = _promote_plane(dense)
    return dense, None


def _padded_planes(x: DNDarray):
    """PADDED (re, im) planes with canonical sharding (for shard_map)."""
    if x._planar is not None:
        return x._planar
    if types.heat_type_is_complexfloating(x.dtype):
        re, im = _planes_in(x)
        return _repad(re, x.shape, x.split, x.comm), _repad(im, x.shape, x.split, x.comm)
    buf = _promote_plane(x.larray_padded)
    return buf, jnp.zeros_like(buf)


def _repad(plane, gshape, split, comm):
    if split is None:
        return jax.device_put(plane, comm.sharding(None))
    pad = comm.pad_amount(gshape[split])
    if pad:
        widths = [(0, pad if d == split else 0) for d in range(plane.ndim)]
        plane = jnp.pad(plane, widths)
    return jax.device_put(plane, comm.sharding(split))


def _wrap_planar(x: DNDarray, re, im, split) -> DNDarray:
    gshape = tuple(int(s) for s in re.shape)
    if split is not None and split >= len(gshape):
        split = None
    re = _repad(re, gshape, split, x.comm)
    im = _repad(im, gshape, split, x.comm)
    return DNDarray.from_planar(re, im, gshape, split, x.device, x.comm)


def _planar_prog(kind: str, norm, axes_ns):
    """One jitted program for a whole transform chain (no eager tails,
    each of which would be its own host dispatch).  The FFT env
    knobs are part of the cache key: toggling HEAT_TPU_FFT_INTERLEAVED /
    _PRECISION / _PALLAS mid-process must reach the next call instead of
    silently returning a program traced under the old configuration."""
    cfg = tuple(
        _os.environ.get(k, "")
        for k in (
            "HEAT_TPU_FFT_INTERLEAVED",
            "HEAT_TPU_FFT_PRECISION",
            "HEAT_TPU_FFT_PALLAS",
            "HEAT_TPU_FFT_LEADING",
            "HEAT_TPU_FFT_EXT_PALLAS",
            "HEAT_TPU_FFT_STAGE_PALLAS",
            "HEAT_TPU_FFT_DIRECT_CAP",
            "HEAT_TPU_FFT_CUTOFF",
        )
    )
    return _planar_prog_cached(kind, norm, axes_ns, cfg)


@_functools.lru_cache(maxsize=256)
def _planar_prog_cached(kind: str, norm, axes_ns, _cfg):

    def run(re, im):
        if kind in ("fft", "ifft"):
            inv = kind == "ifft"
            if (
                not inv
                and im is None
                and len(axes_ns) >= 2
                and all(n is None for _, n in axes_ns)
            ):
                # real input, full lengths: half-spectrum + Hermitian
                # extension saves ~40% of the MXU work
                return _pl.real_fftn(re, [a for a, _ in axes_ns], norm)
            if len(axes_ns) in (2, 3) and all(n is None for _, n in axes_ns):
                axes_l = [a for a, _ in axes_ns]
                if im is not None and _pl._interleaved_eligible(re, axes_l):
                    # complex input, full lengths: the pair-block leading
                    # engine when eligible (fftn -> filter -> ifftn chains
                    # stay on the fast path, not just the first transform),
                    # else the interleaved one-dot-per-stage engine
                    from . import _leading

                    if _leading.leading_eligible(re, axes_l, True):
                        return _leading.cfftn_leading(re, im, inv, norm)
                    if re.ndim == 3:
                        return _pl.cfft3_interleaved(re, im, inv, norm)
                    return _pl.cfft2_interleaved(re, im, inv, norm)
                if im is None and inv and _pl._interleaved_eligible(re, axes_l):
                    # ifftn of a REAL array: conj(fft(x))/N — one real
                    # forward pass through the half-spectrum engine
                    fre, fim = _pl.real_fftn(re, axes_l, None)
                    return _pl._scaled(
                        fre, -fim,
                        _pl.scale_factor([re.shape[a] for a in axes_l], norm, True),
                    )
            for a, n in axes_ns:
                re, im = _pl.fft1(re, im, a, n, norm, inv)
            return re, im
        if kind in ("rfft", "ihfft"):
            if (
                im is None
                and len(axes_ns) in (2, 3)
                and all(n is None for _, n in axes_ns)
                and tuple(a for a, _ in axes_ns) == tuple(range(len(axes_ns)))
                and _pl._interleaved_eligible(re, [a for a, _ in axes_ns])
            ):
                # rfftn/rfft2: the interleaved engine stopped at the half
                # spectrum — strictly cheaper than the full transform.
                # ihfftn rides the same pass: conj(rfftn)/N (inverse
                # transforms conj-commute axis by axis)
                half = (
                    _pl.rfft3_half_interleaved if re.ndim == 3 else _pl.rfft2_half_interleaved
                )
                if kind == "rfft":
                    return half(re, norm)
                fre, fim = half(re, None)
                s = _pl.scale_factor(list(re.shape), norm, True)
                return _pl._scaled(fre, -fim, s)
            last_a, last_n = axes_ns[-1]
            op = _pl.rfft1 if kind == "rfft" else _pl.ihfft1
            re, im = op(re, last_a, last_n, norm)
            inv = kind == "ihfft"
            for a, n in axes_ns[:-1]:
                re, im = _pl.fft1(re, im, a, n, norm, inv)
            return re, im
        # irfft / hfft: complex passes first, the real-output op last
        inv = kind == "irfft"
        if (
            im is not None
            and len(axes_ns) in (2, 3)
            and all(n is None for _, n in axes_ns[:-1])
            and tuple(a for a, _ in axes_ns) == tuple(range(len(axes_ns)))
            and _pl._interleaved_eligible(re, [a for a, _ in axes_ns])
        ):
            n_out = axes_ns[-1][1]
            n_out = int(n_out) if n_out is not None else 2 * (re.shape[-1] - 1)
            if n_out >= 2:
                ir = (
                    _pl.irfft3_interleaved if re.ndim == 3 else _pl.irfft2_interleaved
                )
                if kind == "irfft":
                    return ir(re, im, n_out, norm), None
                # hfftn = irfftn(conj a) * N with forward-family norms:
                # run the c2r engine unscaled, apply hfft's own family
                lengths = list(re.shape[:-1]) + [n_out]
                out = ir(re, -im, n_out, "forward")  # inverse-forward = x1
                s = _pl.scale_factor(lengths, norm, False)
                return _pl._scaled(out, None, s)[0], None
        for a, n in axes_ns[:-1]:
            re, im = _pl.fft1(re, im, a, n, norm, inv)
        last_a, last_n = axes_ns[-1]
        op = _pl.irfft1 if kind == "irfft" else _pl.hfft1
        return op(re, im, last_a, last_n, norm), None

    return jax.jit(run)


def _pencil_out_len(op_kind: str, n_true: int, n_param) -> int:
    """Global output length along the transform axis (numpy semantics)."""
    if op_kind in ("fft", "ifft"):
        return n_param if n_param is not None else n_true
    if op_kind in ("rfft", "ihfft"):
        n = n_param if n_param is not None else n_true
        return n // 2 + 1
    # irfft / hfft: Hermitian input of length m -> real signal of n_out
    return n_param if n_param is not None else 2 * (n_true - 1)


@_functools.lru_cache(maxsize=256)
def _pencil_planar_kind_fn(
    comm, op_kind: str, axis: int, partner: int, n_true: int, n_param, ndim: int,
    norm, have_im: bool,
):
    """Generalized planar pencil: ANY transform kind along the split axis
    rides two all_to_alls (one per live plane) instead of a gather, with
    explicit-``n`` fitting and the Hermitian length bookkeeping INSIDE the
    shard_map body (VERDICT r3 #4).  Real-input kinds ship one plane in,
    real-output kinds ship one plane back — half the traffic of the
    complex case."""
    from jax.sharding import PartitionSpec as _P

    name = comm.axis_name
    spec = _P(*[name if d == axis else None for d in range(ndim)])
    m_out = _pencil_out_len(op_kind, n_true, n_param)
    m_pad = comm.padded_extent(m_out)

    def run(*planes):
        re = planes[0]
        im = planes[1] if have_im else None
        tre = jax.lax.all_to_all(re, name, split_axis=partner, concat_axis=axis, tiled=True)
        tim = (
            jax.lax.all_to_all(im, name, split_axis=partner, concat_axis=axis, tiled=True)
            if have_im
            else None
        )
        idx = tuple(slice(0, n_true) if d == axis else slice(None) for d in range(ndim))
        tre = tre[idx]
        tim = tim[idx] if have_im else None
        if op_kind in ("fft", "ifft"):
            ore, oim = _pl.fft1(tre, tim, axis, n_param, norm, op_kind == "ifft")
        elif op_kind == "rfft":
            ore, oim = _pl.rfft1(tre, axis, n_param, norm)
        elif op_kind == "ihfft":
            ore, oim = _pl.ihfft1(tre, axis, n_param, norm)
        elif op_kind == "irfft":
            ore, oim = _pl.irfft1(tre, tim, axis, n_param, norm), None
        else:  # hfft
            ore, oim = _pl.hfft1(tre, tim, axis, n_param, norm), None
        widths = [(0, m_pad - m_out) if d == axis else (0, 0) for d in range(ndim)]
        ore = jnp.pad(ore, widths)
        rre = jax.lax.all_to_all(ore, name, split_axis=axis, concat_axis=partner, tiled=True)
        if oim is None:
            return (rre,)
        oim = jnp.pad(oim, widths)
        rim = jax.lax.all_to_all(oim, name, split_axis=axis, concat_axis=partner, tiled=True)
        return (rre, rim)

    n_in = 2 if have_im else 1
    n_out = 1 if op_kind in ("irfft", "hfft") else 2
    return jax.jit(
        _shard_map(
            run, mesh=comm.mesh, in_specs=(spec,) * n_in, out_specs=(spec,) * n_out
        )
    )


def _pencil_pick_partner(gshape, split: int, comm) -> Optional[int]:
    """Partner axis for the pencil all_to_all: a divisible axis if one
    exists, else the axis with the least relative padding (the padded
    partner replaces the r3 GSPMD-reshard fallback).  None only for 1-D."""
    best, best_frac = None, None
    for d in range(len(gshape)):
        if d == split:
            continue
        pad = comm.pad_amount(gshape[d])
        if pad == 0:
            return d
        frac = pad / (gshape[d] + pad)
        if best is None or frac < best_frac:
            best, best_frac = d, frac
    return best


def _pencil_apply_planar(re, im, gshape, split, op_kind, n_param, norm, comm):
    """One split-axis transform via the pencil, on PADDED planes.

    Returns (planes tuple, new gshape) — planes has one element for the
    real-output kinds.  Handles a non-divisible partner by locally padding
    that axis before the program and slicing after (padding a non-split
    axis moves no data between devices)."""
    ndim = len(gshape)
    partner = _pencil_pick_partner(gshape, split, comm)
    ppad = comm.pad_amount(gshape[partner])
    if ppad:
        widths = [(0, ppad) if d == partner else (0, 0) for d in range(ndim)]
        re = jnp.pad(re, widths)
        im = jnp.pad(im, widths) if im is not None else None
    fn = _pencil_planar_kind_fn(
        comm, op_kind, split, partner, gshape[split], n_param, ndim, norm,
        im is not None,
    )
    out = fn(re, im) if im is not None else fn(re)
    if ppad:
        sl = tuple(
            slice(0, gshape[d]) if d == partner else slice(None) for d in range(ndim)
        )
        out = tuple(o[sl] for o in out)
        out = tuple(jax.device_put(o, comm.sharding(split)) for o in out)
    m_out = _pencil_out_len(op_kind, gshape[split], n_param)
    new_gshape = tuple(m_out if d == split else s for d, s in enumerate(gshape))
    return out, new_gshape


def _planar_entry(x: DNDarray, kind: str, axes_ns, norm) -> DNDarray:
    """Planar transform chain; split-axis complex passes use the pencil."""
    if kind in ("rfft", "ihfft") and types.heat_type_is_complexfloating(x.dtype):
        # numpy raises here; silently dropping the imaginary plane would
        # diverge from every non-planar configuration
        raise TypeError(f"{kind} requires a real-typed DNDarray, is {x.dtype.__name__}")
    axes_ns = tuple((int(a), None if n is None else int(n)) for a, n in axes_ns)
    y = x
    split_hit = (
        y.split is not None
        and y.comm.size > 1
        and y.ndim >= 2
        and any(a == y.split for a, _ in axes_ns)
    )
    if split_hit:
        return _planar_split_chain(y, kind, axes_ns, norm)
    re, im = _planes_in(y)
    out_re, out_im = _planar_prog(kind, norm, axes_ns)(re, im)
    split = y.split
    if out_im is None:  # real output (irfft/hfft)
        if split is not None and split >= out_re.ndim:
            split = None
        return DNDarray.from_dense(out_re, split, y.device, y.comm)
    return _wrap_planar(y, out_re, out_im, split)


def _planar_split_chain(y: DNDarray, kind: str, axes_ns, norm) -> DNDarray:
    """Transform chain for arrays split along one of the transform axes:
    the split-axis pass (ANY kind, ANY ``n``) rides the generalized
    planar pencil; every other pass runs as a local per-axis program on
    the PADDED planes (axis != split, so the canonical split padding is
    never mixed in — no reshard between passes).  Covers all 8 kinds
    without a single all-gather (VERDICT r3 #4)."""
    comm, device, split = y.comm, y.device, y.split
    # ordered per-axis op list with numpy's execution order for each kind
    if kind in ("fft", "ifft"):
        ops = [(kind, a, n) for a, n in axes_ns]
    elif kind in ("rfft", "ihfft"):
        rest = "fft" if kind == "rfft" else "ifft"
        ops = [(kind, *axes_ns[-1])] + [(rest, a, n) for a, n in axes_ns[:-1]]
    else:  # irfft / hfft: complex passes first, real-output op last
        rest = "ifft" if kind == "irfft" else "fft"
        ops = [(rest, a, n) for a, n in axes_ns[:-1]] + [(kind, *axes_ns[-1])]

    re, im = _padded_planes(y)
    if kind in ("rfft", "ihfft"):
        im = None  # real input: ship/transform one plane
        re = _promote_plane(re)
    gshape = y.shape
    for op_kind, a, n in ops:
        real_out = op_kind in ("irfft", "hfft")
        if a == split:
            planes, gshape = _pencil_apply_planar(
                re, im, gshape, split, op_kind, n, norm, comm
            )
            re = planes[0]
            im = planes[1] if len(planes) == 2 else None
        else:
            prog = _planar_prog(op_kind, norm, ((a, n),))
            out = prog(re, im)
            re, im = (out[0], out[1]) if isinstance(out, tuple) else out
            m_out = _pencil_out_len(op_kind, gshape[a], n)
            gshape = tuple(m_out if d == a else s for d, s in enumerate(gshape))
        if real_out:
            im = None
    dtype = types.canonical_heat_type(re.dtype)
    if im is None and ops[-1][0] in ("irfft", "hfft"):
        return DNDarray(re, gshape, dtype, split, device, comm)
    if im is None:  # fft of a real input produced no explicit imag plane
        im = jnp.zeros_like(re)
    return DNDarray.from_planar(re, im, gshape, split, device, comm)


# ----------------------------------------------------------------------
# 1-D transforms (fft.py:299-420)
# ----------------------------------------------------------------------
# ----------------------------------------------------------------------
# pencil decomposition: FFT along the split axis WITHOUT gathering.
# GSPMD lowers a split-axis FFT to an all-gather (every device pays the
# full array); the pencil program instead all_to_all-transposes so the
# transform axis becomes device-local, runs the local FFT, and transposes
# back — p x less traffic and O(N/p) memory, the reference's pencil
# resplit (fft.py:100-137) as one shard_map program.
# ----------------------------------------------------------------------
import functools as _functools


def _pencil_partner(x: DNDarray, axis: int, n) -> Optional[int]:
    """Axis to trade in the all_to_all transpose, or None if ineligible."""
    comm = x.comm
    if comm.size <= 1 or x.split != axis or x.ndim < 2 or n is not None:
        return None
    for d in range(x.ndim):
        if d != axis and x.shape[d] % comm.size == 0:
            return d
    return None


@_functools.lru_cache(maxsize=128)
def _pencil_fn(comm, kind: str, axis: int, partner: int, n_true: int, ndim: int, norm):
    """Jitted, cached pencil-FFT executable."""
    name = comm.axis_name
    fft_op = getattr(jnp.fft, kind)
    spec = P(*[name if d == axis else None for d in range(ndim)])

    def body(blk):
        # blk: (.., padded_n/p at axis, .., full at partner, ..)
        t = jax.lax.all_to_all(blk, name, split_axis=partner, concat_axis=axis, tiled=True)
        # transform axis is now full locally; padding rows are excluded
        # from the transform and re-appended (don't-care bytes)
        idx = tuple(slice(0, n_true) if d == axis else slice(None) for d in range(ndim))
        res = fft_op(t[idx], axis=axis, norm=norm)
        widths = [(0, t.shape[axis] - n_true) if d == axis else (0, 0) for d in range(ndim)]
        res = jnp.pad(res, widths)
        return jax.lax.all_to_all(res, name, split_axis=axis, concat_axis=partner, tiled=True)

    return jax.jit(
        _shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec)
    )


def _pencil_transform(x: DNDarray, kind: str, axis: int, partner: int, norm) -> DNDarray:
    from ..core.dndarray import DNDarray as _D

    blk = x.larray_padded
    if not types.heat_type_is_inexact(x.dtype):
        blk = blk.astype(jnp.float32)
    out = _pencil_fn(x.comm, kind, axis, partner, x.shape[axis], x.ndim, norm)(blk)
    return _D(out, x.shape, types.canonical_heat_type(out.dtype), axis, x.device, x.comm)


def fft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """1-D complex FFT along ``axis`` (fft.py:310)."""
    _check(x)
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "fft", ((axis, n),), norm)
    partner = _pencil_partner(x, axis, n)
    if partner is not None:
        return _pencil_transform(x, "fft", axis, partner, norm)
    result = jnp.fft.fft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


def ifft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """1-D inverse FFT (fft.py:575)."""
    _check(x)
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "ifft", ((axis, n),), norm)
    partner = _pencil_partner(x, axis, n)
    if partner is not None:
        return _pencil_transform(x, "ifft", axis, partner, norm)
    result = jnp.fft.ifft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


def rfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Real-input FFT; output truncated at Nyquist (fft.py:878)."""
    _check(x)
    if types.heat_type_is_complexfloating(x.dtype):
        raise TypeError(f"x must be a real-typed DNDarray, is {x.dtype.__name__}")
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "rfft", ((axis, n),), norm)
    result = jnp.fft.rfft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


def irfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Inverse of rfft, real output (fft.py:700)."""
    _check(x)
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "irfft", ((axis, n),), norm)
    result = jnp.fft.irfft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


def hfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """FFT of a Hermitian-symmetric signal (fft.py:478)."""
    _check(x)
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "hfft", ((axis, n),), norm)
    result = jnp.fft.hfft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


def ihfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Inverse Hermitian FFT (fft.py:651)."""
    _check(x)
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, "ihfft", ((axis, n),), norm)
    result = jnp.fft.ihfft(_complex_dense(x), n=n, axis=axis, norm=norm)
    return _wrap(x, result)


# ----------------------------------------------------------------------
# 2-D / N-D transforms (fft.py:139-298 __fftn_op callers)
# ----------------------------------------------------------------------
def _axes2(x, axes):
    if axes is None:
        axes = (-2, -1)
    return tuple(sanitize_axis(x.shape, a) for a in axes)


def _nd_axes(arr, s, axes):
    """NumPy-style (s, axes) normalization for n-D transforms."""
    nd = arr.ndim
    if axes is None:
        axes = tuple(range(nd)) if s is None else tuple(range(nd - len(s), nd))
    else:
        axes = tuple(a % nd for a in axes)
    if s is None:
        s = (None,) * len(axes)
    return tuple(s), axes


def _hermitian_fftn(arr, s, axes, norm, kind: str):
    """n-D ``hfft`` / ``ihfft`` as chained 1-D calls (jnp has no
    hfftn/ihfftn).  Separable transforms compose per axis and every
    supported norm ('ortho', 'forward', backward) factorizes per axis, so
    the chain is exact.  The Hermitian transform runs on the final axis;
    for ``hfft`` the complex passes run FIRST (the real-output transform
    discards the imaginary part).  Identities verified against
    torch.fft.hfftn/ihfftn for all norms."""
    s, axes = _nd_axes(arr, s, axes)
    rest = list(zip(axes, s))[:-1]
    if kind == "ihfft":
        arr = jnp.fft.ihfft(arr, n=s[-1], axis=axes[-1], norm=norm)
        for ax, n in rest:
            arr = jnp.fft.ifft(arr, n=n, axis=ax, norm=norm)
        return arr
    for ax, n in rest:
        arr = jnp.fft.fft(arr, n=n, axis=ax, norm=norm)
    return jnp.fft.hfft(arr, n=s[-1], axis=axes[-1], norm=norm)


def _axes_ns_of(x, s, axes) -> tuple:
    """(axis, n) pairs with numpy (s, axes) normalization."""
    s2, axes2 = _nd_axes(x, s, axes)
    return tuple(zip(axes2, s2))


def fft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D FFT (fft.py:352)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "fft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    result = jnp.fft.fft2(_complex_dense(x), s=s, axes=_axes2(x, axes), norm=norm)
    return _wrap(x, result)


def ifft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse FFT (fft.py:606)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "ifft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    result = jnp.fft.ifft2(_complex_dense(x), s=s, axes=_axes2(x, axes), norm=norm)
    return _wrap(x, result)


def _pencil_nd(x: DNDarray, kind: str, s, axes, norm):
    """Pencil the split axis first, then transform the remaining (local)
    axes — no axis of the n-D transform ever gathers.  Norms compose
    because fftn's scaling factorizes per axis.  Returns None when the
    pencil path doesn't apply."""
    if s is not None:
        return None
    axes_eff = axes if axes is not None else tuple(range(x.ndim))
    if x.split not in axes_eff:
        return None
    partner = _pencil_partner(x, x.split, None)
    if partner is None:
        return None
    y = _pencil_transform(x, kind, x.split, partner, norm)
    rest = tuple(a for a in axes_eff if a != x.split)
    if not rest:
        return y
    nd_op = jnp.fft.fftn if kind == "fft" else jnp.fft.ifftn
    return _wrap(y, nd_op(_complex_dense(y), axes=rest, norm=norm))


def fftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D FFT — the pencil-decomposition workhorse (fft.py:383)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "fft", _axes_ns_of(x, s, axes), norm)
    pencil = _pencil_nd(x, "fft", s, axes, norm)
    if pencil is not None:
        return pencil
    return _wrap(x, jnp.fft.fftn(_complex_dense(x), s=s, axes=axes, norm=norm))


def ifftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse FFT (fft.py:628)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "ifft", _axes_ns_of(x, s, axes), norm)
    pencil = _pencil_nd(x, "ifft", s, axes, norm)
    if pencil is not None:
        return pencil
    return _wrap(x, jnp.fft.ifftn(_complex_dense(x), s=s, axes=axes, norm=norm))


def rfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D real FFT (fft.py:922)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "rfft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    result = jnp.fft.rfft2(_complex_dense(x), s=s, axes=_axes2(x, axes), norm=norm)
    return _wrap(x, result)


def irfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse real FFT (fft.py:744)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "irfft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    result = jnp.fft.irfft2(_complex_dense(x), s=s, axes=_axes2(x, axes), norm=norm)
    return _wrap(x, result)


def rfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D real FFT (fft.py:953)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "rfft", _axes_ns_of(x, s, axes), norm)
    return _wrap(x, jnp.fft.rfftn(_complex_dense(x), s=s, axes=axes, norm=norm))


def irfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse real FFT (fft.py:775)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "irfft", _axes_ns_of(x, s, axes), norm)
    return _wrap(x, jnp.fft.irfftn(_complex_dense(x), s=s, axes=axes, norm=norm))


def hfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D Hermitian FFT (fft.py:509)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "hfft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    return _wrap(x, _hermitian_fftn(_complex_dense(x), s, _axes2(x, axes), norm, "hfft"))


def hfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D Hermitian FFT (fft.py:540)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "hfft", _axes_ns_of(x, s, axes), norm)
    return _wrap(x, _hermitian_fftn(_complex_dense(x), s, axes, norm, "hfft"))


def ihfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse Hermitian FFT (fft.py:672)."""
    _check(x)
    if _use_planar():
        return _planar_entry(x, "ihfft", _axes_ns_of(x, s, _axes2(x, axes)), norm)
    return _wrap(x, _hermitian_fftn(_complex_dense(x), s, _axes2(x, axes), norm, "ihfft"))


def ihfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse Hermitian FFT (fft.py:686)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in axes)
    if _use_planar():
        return _planar_entry(x, "ihfft", _axes_ns_of(x, s, axes), norm)
    return _wrap(x, _hermitian_fftn(_complex_dense(x), s, axes, norm, "ihfft"))


# ----------------------------------------------------------------------
# helpers (fft.py:421-477, 806-877)
# ----------------------------------------------------------------------
def fftfreq(n: int, d: float = 1.0, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Sample frequencies of fft (fft.py:421)."""
    from ..core import factories

    result = jnp.fft.fftfreq(n, d=d)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_type())
    else:
        result = result.astype(jnp.float32)
    return factories.array(result, split=split, device=device, comm=comm)


def rfftfreq(n: int, d: float = 1.0, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Sample frequencies of rfft (fft.py:846)."""
    from ..core import factories

    result = jnp.fft.rfftfreq(n, d=d)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_type())
    else:
        result = result.astype(jnp.float32)
    return factories.array(result, split=split, device=device, comm=comm)


def fftshift(x: DNDarray, axes=None) -> DNDarray:
    """Shift zero-frequency to the center (fft.py:450; implemented with
    roll in the reference — XLA's collective permute here)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in (axes if isinstance(axes, (tuple, list)) else (axes,)))
    if x._planar is not None:
        re, im = _planes_in(x)
        return _wrap_planar(
            x, jnp.fft.fftshift(re, axes=axes), jnp.fft.fftshift(im, axes=axes), x.split
        )
    result = jnp.fft.fftshift(x._dense(), axes=axes)
    return _wrap(x, result)


def ifftshift(x: DNDarray, axes=None) -> DNDarray:
    """Inverse of fftshift (fft.py:570)."""
    _check(x)
    if axes is not None:
        axes = tuple(sanitize_axis(x.shape, a) for a in (axes if isinstance(axes, (tuple, list)) else (axes,)))
    if x._planar is not None:
        re, im = _planes_in(x)
        return _wrap_planar(
            x, jnp.fft.ifftshift(re, axes=axes), jnp.fft.ifftshift(im, axes=axes), x.split
        )
    result = jnp.fft.ifftshift(x._dense(), axes=axes)
    return _wrap(x, result)
