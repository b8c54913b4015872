"""Distributed FFT, analog of heat/fft/fft.py (22 exports).

The reference implements pencil-decomposition FFT by hand: a transform
along the split axis transposes that axis to 0, resplits to 1 (an MPI
Alltoallw with subarray datatypes), runs the local torch FFT, and resplits
back (``__fft_op`` fft.py:40-138, ``__fftn_op`` :139-298).  Here a split
array is transformed by one ``shard_map`` program over its split axis: the
same pencil (``all_to_all``, the transform, ``all_to_all`` back) along the
split axis, a large slab block by block so that one block's exchange is in
flight while another is transformed, and XLA's ``fft`` on each device's own
slab along the others.  A
``jnp.fft.*`` call on the sharded global array is NOT that: GSPMD does not
keep a sharded batch axis through the ``fft`` operation and, on the CPU
mesh, gathers the whole array onto every device (PERF.md, PR 31).
"""

from __future__ import annotations

import contextlib
from typing import Iterable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map

from ..core import types
from ..core.dndarray import DNDarray
from ..core.stride_tricks import sanitize_axis
from ..telemetry.spans import span as _span

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
        tre = comm.all_to_all(re, split_axis=partner, concat_axis=axis)
        tim = comm.all_to_all(im, split_axis=partner, concat_axis=axis) if have_im else None
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
        rre = comm.all_to_all(ore, split_axis=axis, concat_axis=partner)
        if oim is None:
            return (rre,)
        oim = jnp.pad(oim, widths)
        rim = comm.all_to_all(oim, split_axis=axis, concat_axis=partner)
        return (rre, rim)

    n_in = 2 if have_im else 1
    n_out = 1 if op_kind in ("irfft", "hfft") else 2
    return jax.jit(
        _shard_map(
            run, mesh=comm.mesh, in_specs=(spec,) * n_in, out_specs=(spec,) * n_out
        )
    )


def _pencil_pick_partner(gshape, split: int, comm, last: bool = False) -> Optional[int]:
    """Partner axis for the pencil all_to_all: a divisible axis if one
    exists (the first, or with ``last`` the last), else the axis with the
    least relative padding (the padded partner replaces the r3
    GSPMD-reshard fallback).  None only for 1-D."""
    best, best_frac = None, None
    others = [d for d in range(len(gshape)) if d != split]
    for d in reversed(others) if last else others:
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
# split arrays: every transform is ONE ``shard_map`` program over the
# split axis.  GSPMD does not keep a sharded batch axis through XLA's
# ``fft`` operation: ``jnp.fft.*`` on a sharded global array compiles to an
# all-gather of the whole array and a transform of all of it on every
# device (PERF.md, PR 31).  Inside the ``shard_map`` each device sees its
# own slab, whose other axes are whole, so a transform along axes that are
# not split is the slab's own (a ``local`` stage), and a transform along
# the split axis is the pencil: ``all_to_all`` so that the axis becomes
# whole on the device, the transform, ``all_to_all`` back -- the
# reference's pencil resplit (fft.py:100-137), pipelined over blocks of a
# bystander axis where the slab is large (``_pencil_layout``).  No stage gathers.
# ----------------------------------------------------------------------
_COMPLEX_OF = {"fft": "fft", "rfft": "fft", "hfft": "fft", "ifft": "ifft", "ihfft": "ifft", "irfft": "ifft"}


def _stages(kind: str, axes_ns, split: int, norm) -> tuple:
    """The transform as stages in NumPy's order for each kind: the real
    transform of ``rfft`` / ``ihfft`` first and of ``irfft`` / ``hfft`` last,
    on the last axis; between them the complex passes, the split axis's
    (``("pencil", op, n, norm)``) ahead of the local axes', which go as one
    n-D call (``("locals", op, size, axes, norm)``; a single local axis is
    ``("local", op, n, axis, norm)``: both the arguments of ``jnp.fft.<op>``
    after the array).  ``_planned`` writes each pencil's layout into it."""
    cplx = _COMPLEX_OF[kind]
    head = axes_ns[-1:] if kind in ("rfft", "ihfft") else ()
    tail = axes_ns[-1:] if kind in ("irfft", "hfft") else ()
    mid = axes_ns[: len(axes_ns) - len(head) - len(tail)]

    def one(op, a, n):
        return ("pencil", op, n, norm) if a == split else ("local", op, n, a, norm)

    stages = [one(kind, a, n) for a, n in head]
    stages += [one(cplx, a, n) for a, n in mid if a == split]
    local = [(a, n) for a, n in mid if a != split]
    if local:
        stages.append(_locals(cplx + "n", local, norm))
    stages += [one(kind, a, n) for a, n in tail]
    return tuple(stages)


def _locals(op: str, axes_ns, norm) -> tuple:
    """The n-D stage of ``op`` over ``(axis, n)`` pairs; no sizes where no axis has one."""
    size = None if all(n is None for _, n in axes_ns) else tuple(n for _, n in axes_ns)
    return ("locals", op, size, tuple(a for a, _ in axes_ns), norm)


#: The pencil is pipelined: a slab is cut along a bystander axis (neither the
#: split axis nor the partner) into blocks that are exchanged in, transformed
#: and exchanged back on their own, so that the compiler keeps one block's
#: exchange in flight while another is transformed.  By step 0 of PR 32 (four
#: v5e chips, PERF.md section 6): a block's plane (its real or its imaginary
#: part) of 16 MiB or more, at most 16 blocks (1024^3 float32 over four
#: chips: 16 blocks of 64 MiB, 166.8 ms for the one block's 187.9; 512^3:
#: 8 of 16 MiB, 21.9 for 27.1), and the blocks cut along a MAJOR axis, the
#: partner being the last axis the mesh divides (166.8 against 173.2 ms cut
#: along the minor one).
_PENCIL_BLOCK_BYTES = 16 << 20
_PENCIL_BLOCKS_MAX = 16


def _pencil_layout(slab, split: int, comm) -> Tuple[int, Optional[int], int]:
    """(partner, the axis the blocks are cut along, blocks) of the pencil of
    ``slab`` (a device's, by shape and dtype), from what the code can see.
    Cut up: the partner is the last axis the mesh divides, the blocks lie
    along the largest axis left (the first of equals), and there are as many
    as the largest power of two, ``_PENCIL_BLOCKS_MAX`` at most, that divides
    that axis and leaves every block's plane ``_PENCIL_BLOCK_BYTES`` or more.
    One block, PR 31's program operation for operation (the first such axis
    the partner, nothing cut): where the slab has no bystander axis (2-D),
    where it is small, and where the mesh's compiler has no option that puts
    an exchange beside compute, since cutting alone only costs (step 0:
    198.4 ms cut in four for the one block's 187.7)."""
    shape = slab.shape
    partner = _pencil_pick_partner(shape, split, comm, last=True)
    others = [d for d in range(len(shape)) if d not in (split, partner)]
    if others and comm.overlap_compiler_options():
        cut = max(others, key=lambda d: shape[d])
        plane = int(np.prod(shape)) * slab.dtype.itemsize // (2 if jnp.issubdtype(slab.dtype, jnp.complexfloating) else 1)
        blocks = _PENCIL_BLOCKS_MAX
        while blocks > 1 and (shape[cut] % blocks or plane // blocks < _PENCIL_BLOCK_BYTES):
            blocks //= 2
        if blocks > 1:
            return partner, cut, blocks
    return _pencil_pick_partner(shape, split, comm), None, 1


def _local(blk, stage: tuple):
    """A ``local`` / ``locals`` stage on a slab or on a block of one."""
    with jax.named_scope("fft.local"):
        return getattr(jnp.fft, stage[1])(blk, *stage[2:])


@_functools.lru_cache(maxsize=256)
def _planned(stages: tuple, comm, split: int, padded_shape: tuple, n_true: int, dtype) -> tuple:
    """``stages`` with every pencil's layout decided, once, here:
    ``("pencil", op, n, norm, partner, cut, blocks, then)`` by
    ``_pencil_layout`` of the slab as it reaches that stage.  Behind a pencil
    that is cut, the ``locals`` stage is taken apart: its axes but ``cut`` go
    to each block as it lands (``then``, a ``locals`` stage or None), and
    ``cut`` alone stays a stage of the whole slab after the pencil."""
    slab = jax.ShapeDtypeStruct(tuple(e // comm.size if d == split else e for d, e in enumerate(padded_shape)),
                                dtype if np.issubdtype(dtype, np.inexact) else np.float32)

    def resized(slab, extent):  # the slab with ``extent`` rows of the split axis
        return jax.ShapeDtypeStruct(tuple(extent if d == split else e for d, e in enumerate(slab.shape)), slab.dtype)

    planned, todo = [], list(stages)
    while todo:
        stage = todo.pop(0)
        if stage[0] == "pencil":
            partner, cut, blocks = _pencil_layout(slab, split, comm)
            then = None
            if blocks > 1 and todo and todo[0][0] == "locals":
                _, op, size, axes, norm = todo.pop(0)
                pairs = list(zip(axes, size or (None,) * len(axes)))
                per_block, whole = ([(a, n) for a, n in pairs if (a == cut) == is_cut] for is_cut in (False, True))
                then = _locals(op, per_block, norm) if per_block else None
                if whole:
                    todo.insert(0, _locals(op, whole, norm))
            stage += (partner, cut, blocks, then)
            whole_axis = jax.eval_shape(lambda b, s=stage: getattr(jnp.fft, s[1])(b, s[2], split, s[3]), resized(slab, n_true))
            n_true = whole_axis.shape[split]
            slab = resized(whole_axis, comm.padded_extent(n_true) // comm.size)
            if then:
                slab = jax.eval_shape(_functools.partial(_local, stage=then), slab)
        else:
            slab = jax.eval_shape(_functools.partial(_local, stage=stage), slab)
        planned.append(stage)
    return tuple(planned)


def _pencil_stage(comm, blk, axis: int, n_true: int, op: str, n, norm, partner: int, cut: Optional[int], blocks: int, then):
    """One transform along the split axis of a slab, laid out as ``_planned``
    wrote it: the partner axis (one the mesh divides if there is one, else
    the one that pads least; padded here, on the device) is traded for the
    split axis, whose canonical padding rows are left out of the transform,
    and traded back; ``then``, if there is one, is the slab's own stage that
    is applied to each block as it comes back.

    In ``blocks`` blocks of the axis ``cut``, as a pipeline: every block's
    way in is issued up front, and block ``i``'s transform waits for block
    ``i - 1``'s (an ``optimization_barrier`` over the two).  Without that
    order the compiler merges the blocks' transforms into one fusion that
    waits for every block's exchange, and nothing is left to run beside one
    (PERF.md section 6, PR 32: at four blocks 184.9 ms a solve merged, 178.7 chained)."""
    n_partner = blk.shape[partner]

    def fit(a, d, extent):  # zero rows up to ``extent`` along ``d``, or the first ``extent`` rows
        if a.shape[d] < extent:
            return jnp.pad(a, [(0, extent - a.shape[d]) if i == d else (0, 0) for i in range(a.ndim)])
        return a[tuple(slice(0, extent) if i == d else slice(None) for i in range(a.ndim))]

    def come_in(piece):
        with jax.named_scope("fft.alltoall.in"):
            t = comm.all_to_all(fit(piece, partner, comm.padded_extent(n_partner)), split_axis=partner, concat_axis=axis)
            return fit(t, axis, n_true)

    def transform(t):
        with jax.named_scope("fft.split_axis"):
            return getattr(jnp.fft, op)(t, n, axis, norm)

    def go_back(res):
        with jax.named_scope("fft.alltoall.out"):
            res = comm.all_to_all(fit(res, axis, comm.padded_extent(res.shape[axis])), split_axis=axis, concat_axis=partner)
            res = fit(res, partner, n_partner)
        return _local(res, then) if then else res

    if blocks == 1:
        res = transform(come_in(blk))
        return go_back(res), res.shape[axis]
    arrived = [come_in(piece) for piece in jnp.split(blk, blocks, axis=cut)]
    res, back = transform(arrived[0]), []
    for t in arrived[1:]:
        res, t = jax.lax.optimization_barrier((res, t))
        back.append(go_back(res))
        res = transform(t)
    back.append(go_back(res))
    return jnp.concatenate(back, axis=cut), res.shape[axis]


@_functools.lru_cache(maxsize=256)
def _slab_program(comm, split: int, ndim: int, n_true: int, stages: tuple):
    """The jitted ``shard_map`` program of ``stages`` (as ``_planned`` leaves
    them) over the split axis: padded array in, padded result out, both with
    the canonical sharding.  A program with a pencil that is cut is compiled
    with the options that let a block's exchange run beside the other
    blocks' transforms (``Communication.overlap_compiler_options``)."""
    spec = comm.sharding(split, ndim).spec

    def body(blk):
        if not jnp.issubdtype(blk.dtype, jnp.inexact):
            blk = blk.astype(jnp.float32)
        n_split = n_true
        for stage in stages:
            if stage[0] == "pencil":
                blk, n_split = _pencil_stage(comm, blk, split, n_split, *stage[1:])
            else:
                blk = _local(blk, stage)
        return blk

    return jax.jit(_shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec),
                   compiler_options=comm.overlap_compiler_options() if _blocks(stages) > 1 else None)


def _blocks(stages: tuple) -> int:
    """What the pencil of planned ``stages`` is cut into; 1 without a pencil."""
    return max((stage[6] for stage in stages if stage[0] == "pencil"), default=1)


def _transform_padded(blk, comm, split: int, n_true: int, stages: tuple):
    """The seam of every split transform: the padded array goes in, the
    padded result comes out (``chipbench/drivers/fftn_pencil.py`` plants its
    faults around this one function)."""
    return _slab_program(comm, split, blk.ndim, n_true, stages)(blk)


def _route(x: DNDarray, axes_ns) -> str:
    """``pencil``: a split array transformed along its split axis;
    ``local``: a split array transformed along other axes only; ``dense``:
    ``jnp.fft`` on the whole array where nothing is split (or one device
    holds it all), and for a 1-D split array, which has no axis to trade
    (it gathers: PERF.md section 7)."""
    if x.split is None or x.comm.size == 1:
        return "dense"
    if all(a != x.split for a, _ in axes_ns):
        return "local"
    return "pencil" if x.ndim >= 2 else "dense"


def _transform(x: DNDarray, kind: str, axes_ns, norm, dense_fn, root: Optional[str] = None) -> DNDarray:
    """Every non-planar entry: ``kind`` over ``axes_ns`` (``(axis, n)``
    pairs) by the route the array's split gives, ``dense_fn`` being the
    ``jnp.fft`` call of the dense route.  The result is split as ``x``.

    Host spans at the layer boundaries, as ``svdtools._hsvd`` carries them:
    ``root`` (the n-D entries name one) is the API layer and holds all but
    the route; ``fft.dispatch`` the array's materialisation, the jit cache
    lookup and the enqueue of the one program; ``fft.wrap`` the result's
    ``DNDarray``.  ``blocks`` on the first two is what the program's pencil
    is cut into, written once the plan is known."""
    route = _route(x, axes_ns)
    with _span(root, shape="x".join(map(str, x.shape)), split=x.split, dtype=x.dtype.__name__, kind=kind, route=route,
               blocks=1) if root else contextlib.nullcontext() as top:
        axes_ns = tuple((int(a), None if n is None else int(n)) for a, n in axes_ns)
        if route == "dense":
            with _span("fft.dispatch", blocks=1):
                result = dense_fn(_complex_dense(x))
            with _span("fft.wrap"):
                return _wrap(x, result)
        split, n_true = x.split, x.shape[x.split]
        stages = _stages(kind, axes_ns, split, norm)
        with _span("fft.dispatch", blocks=1) as enqueue:
            padded = x.larray_padded
            stages = _planned(stages, x.comm, split, padded.shape, n_true, np.dtype(padded.dtype))
            enqueue.attrs["blocks"] = _blocks(stages)
            if top is not None:
                top.attrs["blocks"] = enqueue.attrs["blocks"]
            out = _transform_padded(padded, x.comm, split, n_true, stages)
        with _span("fft.wrap"):
            # the program's result is padded and placed as a DNDarray stores it: nothing is copied
            for stage in stages:
                if stage[0] == "pencil":
                    n_true = _pencil_out_len(stage[1], n_true, stage[2])
            gshape = tuple(n_true if d == split else e for d, e in enumerate(out.shape))
            return DNDarray(out, gshape, types.canonical_heat_type(out.dtype), split, x.device, x.comm)


# ----------------------------------------------------------------------
# 1-D transforms (fft.py:299-420)
# ----------------------------------------------------------------------
def _fft1(x: DNDarray, kind: str, n, axis: int, norm) -> DNDarray:
    _check(x)
    if kind == "rfft" and types.heat_type_is_complexfloating(x.dtype):
        raise TypeError(f"x must be a real-typed DNDarray, is {x.dtype.__name__}")
    axis = sanitize_axis(x.shape, axis)
    if _use_planar():
        return _planar_entry(x, kind, ((axis, n),), norm)
    return _transform(x, kind, ((axis, n),), norm, lambda a: getattr(jnp.fft, kind)(a, n=n, axis=axis, norm=norm))


def fft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """1-D complex FFT along ``axis`` (fft.py:310)."""
    return _fft1(x, "fft", n, axis, norm)


def ifft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """1-D inverse FFT (fft.py:575)."""
    return _fft1(x, "ifft", n, axis, norm)


def rfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Real-input FFT; output truncated at Nyquist (fft.py:878)."""
    return _fft1(x, "rfft", n, axis, norm)


def irfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Inverse of rfft, real output (fft.py:700)."""
    return _fft1(x, "irfft", n, axis, norm)


def hfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """FFT of a Hermitian-symmetric signal (fft.py:478)."""
    return _fft1(x, "hfft", n, axis, norm)


def ihfft(x: DNDarray, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None) -> DNDarray:
    """Inverse Hermitian FFT (fft.py:651)."""
    return _fft1(x, "ihfft", n, axis, norm)


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


def _fftnd(x: DNDarray, kind: str, s, axes, norm, root: Optional[str] = None) -> DNDarray:
    """The 2-D and N-D entries (``axes`` sanitized by the caller)."""
    _check(x)
    axes_ns = _axes_ns_of(x, s, axes)
    if _use_planar():
        return _planar_entry(x, kind, axes_ns, norm)
    if kind in ("hfft", "ihfft"):
        def dense_fn(a):
            return _hermitian_fftn(a, s, axes, norm, kind)
    else:
        def dense_fn(a):
            return getattr(jnp.fft, kind + "n")(a, s=s, axes=axes, norm=norm)
    return _transform(x, kind, axes_ns, norm, dense_fn, root)


def _axes_n(x, axes):
    return None if axes is None else tuple(sanitize_axis(x.shape, a) for a in axes)


def fft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D FFT (fft.py:352)."""
    return _fftnd(x, "fft", s, _axes2(x, axes), norm)


def ifft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse FFT (fft.py:606)."""
    return _fftnd(x, "ifft", s, _axes2(x, axes), norm)


def fftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D FFT (fft.py:383); a split array is never gathered.

    One ``shard_map`` program transforms it: along its split axis by the
    pencil (``all_to_all``, the transform, ``all_to_all`` back), along the
    other axes on each device's own slab; the result is split as ``x``."""
    return _fftnd(x, "fft", s, _axes_n(x, axes), norm, root="ht.fft.fftn")


def ifftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse FFT (fft.py:628); the path is ``fftn``'s."""
    return _fftnd(x, "ifft", s, _axes_n(x, axes), norm, root="ht.fft.ifftn")


def rfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D real FFT (fft.py:922)."""
    return _fftnd(x, "rfft", s, _axes2(x, axes), norm)


def irfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse real FFT (fft.py:744)."""
    return _fftnd(x, "irfft", s, _axes2(x, axes), norm)


def rfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D real FFT (fft.py:953)."""
    return _fftnd(x, "rfft", s, _axes_n(x, axes), norm)


def irfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse real FFT (fft.py:775)."""
    return _fftnd(x, "irfft", s, _axes_n(x, axes), norm)


def hfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D Hermitian FFT (fft.py:509)."""
    return _fftnd(x, "hfft", s, _axes2(x, axes), norm)


def hfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D Hermitian FFT (fft.py:540)."""
    return _fftnd(x, "hfft", s, _axes_n(x, axes), norm)


def ihfft2(x: DNDarray, s=None, axes=(-2, -1), norm=None) -> DNDarray:
    """2-D inverse Hermitian FFT (fft.py:672)."""
    return _fftnd(x, "ihfft", s, _axes2(x, axes), norm)


def ihfftn(x: DNDarray, s=None, axes=None, norm=None) -> DNDarray:
    """N-D inverse Hermitian FFT (fft.py:686)."""
    return _fftnd(x, "ihfft", s, _axes_n(x, axes), norm)


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
