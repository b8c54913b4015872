"""Real-pair (planar) FFT kernels: complex transforms as real matmuls.

The reference's transform semantics (heat/fft/fft.py:40-298) re-expressed
over two REAL planes (re, im) — the engine ``HEAT_TPU_PLANAR=1`` selects;
unset, transforms take ``jnp.fft`` on native complex.  The transform is
built to ride the MXU instead of translating a butterfly network:

* length ``n <= _cutoff()``: the DFT is a literal matrix product with the
  (symmetric) DFT matrix — ``(batch, n) @ (n, n)`` per plane, a shape the
  systolic array is built for.  A complex matmul uses the 3-multiplication
  (Karatsuba) identity, and a purely real input (rfft, the first axis of a
  real fftn) needs only 2 products.
* larger ``n = n1 * n2``: Bailey's four-step factorization — reshape to
  ``(n2, n1)``, DFT the columns, twiddle, DFT the rows, transpose-ravel.
  Each factor recurses until it fits the matmul base case, so every FLOP
  is still a matrix product.
* prime ``n > _cutoff()``: Bluestein's chirp-z algorithm turns the DFT into
  a circular convolution of power-of-two length, which the four-step path
  handles; the chirp filter's spectrum is a host-precomputed constant.

Everything here is pure jnp on real dtypes — traceable, jittable, and
usable inside ``shard_map`` bodies (the pencil program in fft.py).
Accuracy: DFT matrices are built in float64 on the host and applied with a
precision-policy matmul (HIGHEST for f32 planes) — verified against
``np.fft.fftn`` to ~1e-4 relative for float32, full precision for float64.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ._weight_cache import byte_lru as _byte_lru

__all__ = [
    "fft_planes",
    "fftn_planes",
    "real_fftn",
    "scale_factor",
    "fft1",
    "rfft1",
    "irfft1",
    "hfft1",
    "ihfft1",
]

def _cutoff() -> int:
    """Largest DFT applied as one literal matrix product.  The r4
    floor-aware sweep (scripts/tune_fft.py, docs/fft_roofline.md) shows
    the 512³ transform is HBM-bound: the whole (precision × cutoff) grid
    spans only ±12%.  64 is kept for its MXU-friendly K-depth and 1.7e-7
    accuracy at the HIGHEST default; overridable by env for re-tuning on
    other hardware.  Read at call time so the knob participates in
    fft.py's program-cache key (a module-load snapshot would make the
    keyed retrace trace the stale value)."""
    return int(os.environ.get("HEAT_TPU_FFT_CUTOFF", "64"))


def _precision_name() -> str:
    return os.environ.get("HEAT_TPU_FFT_PRECISION", "highest").lower()


def _precision():
    # f32 planes want the 6-pass f32-accurate matmul; f64 planes hit the
    # (software) f64 path where precision flags do not apply
    return {
        "default": jax.lax.Precision.DEFAULT,
        "high": jax.lax.Precision.HIGH,
        "highest": jax.lax.Precision.HIGHEST,
    }[_precision_name()]


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(a, w, precision=_precision())


@_byte_lru
def _dft_w(n: int, inverse: bool, dtype: str):
    """(W_re, W_im, W_re+W_im) for the symmetric n-point DFT matrix."""
    j = np.arange(n, dtype=np.float64)
    # angle built from jk mod n keeps the argument small — cos/sin of huge
    # arguments lose the low bits that ARE the answer
    jk = np.outer(j, j) % n
    ang = 2.0 * np.pi * jk / n
    sign = 1.0 if inverse else -1.0
    wre = np.cos(ang)
    wim = sign * np.sin(ang)
    # NUMPY constants: a jnp array built during a jit trace is a tracer,
    # and caching a tracer poisons every later trace (leak errors); numpy
    # operands are lifted fresh into whichever trace uses them
    return (
        np.asarray(wre, dtype),
        np.asarray(wim, dtype),
        np.asarray(wre + wim, dtype),
    )


@_byte_lru
def _dft_w2(n: int, inverse: bool, dtype: str):
    """(W_re, W_im) only — the direct-dot branch never needs the
    Karatsuba wsum plane, and at the 1024-point cap each cached wsum
    would be ~4 MB of never-read host memory."""
    j = np.arange(n, dtype=np.float64)
    jk = np.outer(j, j) % n
    ang = 2.0 * np.pi * jk / n
    sign = 1.0 if inverse else -1.0
    return np.asarray(np.cos(ang), dtype), np.asarray(sign * np.sin(ang), dtype)


@_byte_lru
def _twiddle(n1: int, n2: int, n: int, inverse: bool, dtype: str):
    """T[j1, k2] = exp(sign * 2*pi*i * j1*k2 / n) for the four-step."""
    j1 = np.arange(n1, dtype=np.float64)
    k2 = np.arange(n2, dtype=np.float64)
    jk = np.outer(j1, k2) % n
    ang = 2.0 * np.pi * jk / n
    sign = 1.0 if inverse else -1.0
    # numpy constants — see _dft_w for why
    return np.asarray(np.cos(ang), dtype), np.asarray(sign * np.sin(ang), dtype)


def _cmul(are, aim, bre, bim):
    """Elementwise planar complex multiply (a may have aim None == real)."""
    if aim is None:
        return are * bre, are * bim
    return are * bre - aim * bim, are * bim + aim * bre


def _apply_w(re, im, w) -> Tuple[jax.Array, jax.Array]:
    """(..., n) @ DFT matrix, 3-mult complex or 2-mult real-input."""
    wre, wim, wsum = w
    if im is None:
        return _mm(re, wre), _mm(re, wim)
    t1 = _mm(re, wre)
    t2 = _mm(im, wim)
    t3 = _mm(re + im, wsum)
    return t1 - t2, t3 - t1 - t2


@functools.lru_cache(maxsize=512)
def _largest_factor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (1 if n is prime past cap)."""
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= cap:
                best = max(best, d)
            q = n // d
            if q <= cap:
                best = max(best, q)
        d += 1
    return best


def _next_pow2(n: int) -> int:
    m = 1
    while m < n:
        m <<= 1
    return m


def _einsum_w(spec: str, re, im, w) -> Tuple[jax.Array, jax.Array]:
    """Karatsuba complex DFT through an einsum spec (transpose folded
    into the dot_general instead of materialized between stages)."""
    wre, wim, wsum = w
    ein = functools.partial(jnp.einsum, spec, precision=_precision())
    if im is None:
        return ein(re, wre), ein(re, wim)
    t1 = ein(re, wre)
    t2 = ein(im, wim)
    t3 = ein(re + im, wsum)
    return t1 - t2, t3 - t1 - t2


def _direct_cap() -> int:
    """Largest n transformed as ONE direct DFT dot per plane (r5).

    The four-step chain materializes many intermediate passes; on the
    bench v5e a (16384, 1024) batched rfft measured 0.60 ms as two
    direct plane dots vs 2.18 ms through the chain (complex: 4-dot
    schoolbook beat the Karatsuba chain 3.11 -> ~1.3).  The O(n^2)
    extra MXU work is invisible below this cap because the transform is
    bandwidth-bound; the (n, n) plane matrices stay <= 4 MB."""
    return int(os.environ.get("HEAT_TPU_FFT_DIRECT_CAP", "1024"))


def _fft_last(re, im, inverse: bool) -> Tuple[jax.Array, jax.Array]:
    """Unscaled DFT along the LAST axis; im may be None (real input)."""
    n = re.shape[-1]
    dt = str(re.dtype)
    cutoff = _cutoff()
    if n == 1:
        return re, jnp.zeros_like(re) if im is None else im
    if n <= cutoff:
        return _apply_w(re, im, _dft_w(n, inverse, dt))
    use_direct = n <= _direct_cap() and re.dtype == jnp.float32
    if use_direct and os.environ.get("HEAT_TPU_FFT_PALLAS", "0") != "1":
        # direct plane dots (any n, primes included — below the cap the
        # Bluestein machinery is never needed): real input 2 dots,
        # complex 4-dot schoolbook — fewer materialized passes than
        # Karatsuba's triple + combines for batched minor-axis work.
        # An explicit HEAT_TPU_FFT_PALLAS=1 opt-in outranks this branch
        # (the fused-kernel path below must stay measurable).
        wre, wim = _dft_w2(n, inverse, dt)
        if im is None:
            return _mm(re, wre), _mm(re, wim)
        return _mm(re, wre) - _mm(im, wim), _mm(re, wim) + _mm(im, wre)
    n1 = _largest_factor(n, cutoff)
    if n1 == 1:
        return _bluestein_last(re, im, inverse)
    # fused Pallas axis pass (OPT-IN, time-neutral on the bench v5e —
    # docs/fft_roofline.md): both stages + twiddle in one VMEM round-trip;
    # import only behind the env gate so the XLA path never depends on
    # the pallas module being importable
    if re.dtype == jnp.float32 and os.environ.get("HEAT_TPU_FFT_PALLAS", "0") == "1":
        try:
            from . import _pallas_fft as _pf
        except ImportError:  # pragma: no cover - pallas-less jax build
            _pf = None
        b_el = 1
        for s in re.shape[:-1]:
            b_el *= int(s)
        if _pf is not None and b_el > 0 and _pf.eligible(n, b_el, re.dtype):
            return _pf.fused_axis_pass(re, im, inverse, _precision_name())
    n2 = n // n1
    batch = re.shape[:-1]
    if n2 <= cutoff:
        # single-level four-step fully inside two einsums: the stage
        # transposes ride the dot_general layouts instead of separate
        # transpose passes — the transform is HBM-bound on the bench chip
        # (see the _cutoff note), so bytes not moved are time saved.
        # j = j1 + n1*j2: x[..., j2, j1]; A: DFT over j2 -> [..., k2, j1]
        re = re.reshape(*batch, n2, n1)
        im = im.reshape(*batch, n2, n1) if im is not None else None
        re, im = _einsum_w("...ji,jk->...ki", re, im, _dft_w(n2, inverse, dt))
        tw_re, tw_im = _twiddle(n1, n2, n, inverse, dt)  # [j1, k2]
        re, im = _cmul(re, im, tw_re.T, tw_im.T)  # planes are [..., k2, j1]
        # B: DFT over j1, output laid out [..., k1, k2] so the C-order
        # ravel IS the k = k2 + n2*k1 output order
        re, im = _einsum_w("...kj,jl->...lk", re, im, _dft_w(n1, inverse, dt))
        return re.reshape(*batch, n), im.reshape(*batch, n)
    # deep factorization: recursive swapaxes formulation
    # j = j1 + n1*j2: C-order reshape puts x[j] at [..., j2, j1]
    re = re.reshape(*batch, n2, n1).swapaxes(-1, -2)  # (..., j1, j2)
    im = im.reshape(*batch, n2, n1).swapaxes(-1, -2) if im is not None else None
    re, im = _fft_last(re, im, inverse)  # DFT over j2 -> (..., j1, k2)
    re, im = _cmul(re, im, *_twiddle(n1, n2, n, inverse, dt))
    re = re.swapaxes(-1, -2)  # (..., k2, j1)
    im = im.swapaxes(-1, -2)
    re, im = _fft_last(re, im, inverse)  # DFT over j1 -> (..., k2, k1)
    # output index k = k2 + n2*k1: ravel of the (k1, k2) layout
    re = re.swapaxes(-1, -2).reshape(*batch, n)
    im = im.swapaxes(-1, -2).reshape(*batch, n)
    return re, im


@_byte_lru
def _bluestein_consts(n: int, inverse: bool, dtype: str):
    """Chirp and the precomputed spectrum of the chirp filter."""
    m = _next_pow2(2 * n - 1)
    j = np.arange(n, dtype=np.int64)
    # j^2 mod 2n keeps the chirp angle small and exact
    ang = np.pi * ((j * j) % (2 * n)).astype(np.float64) / n
    sign = 1.0 if inverse else -1.0
    # c[j] = e^{sign*i*pi*j^2/n}: c[j]*c[k]*conj(c[k-j]) = e^{sign*2*pi*i*jk/n}
    chirp = np.cos(ang) + 1j * sign * np.sin(ang)
    a_mul = chirp  # applied to the input and to the output
    b = np.zeros(m, dtype=np.complex128)
    conj_c = np.conj(chirp)
    b[:n] = conj_c
    b[m - n + 1:] = conj_c[1:n][::-1]  # b[m-j] = conj(c[j])
    B = np.fft.fft(b)  # host constant — never touches the device
    # numpy constants — see _dft_w for why
    return (
        np.asarray(a_mul.real, dtype),
        np.asarray(a_mul.imag, dtype),
        np.asarray(B.real, dtype),
        np.asarray(B.imag, dtype),
        m,
    )


def _bluestein_last(re, im, inverse: bool) -> Tuple[jax.Array, jax.Array]:
    """Chirp-z DFT for prime n past the matmul cutoff (last axis)."""
    n = re.shape[-1]
    are, aim, Bre, Bim, m = _bluestein_consts(n, inverse, str(re.dtype))
    xre, xim = _cmul(re, im, are, aim)
    pad = [(0, 0)] * (xre.ndim - 1) + [(0, m - n)]
    xre, xim = jnp.pad(xre, pad), jnp.pad(xim, pad)
    Xre, Xim = _fft_last(xre, xim, False)  # m is a power of two -> four-step
    Cre, Cim = _cmul(Xre, Xim, Bre, Bim)
    cre, cim = _fft_last(Cre, Cim, True)
    cre, cim = cre[..., :n] / m, cim[..., :n] / m  # unscaled inverse
    return _cmul(cre, cim, are, aim)


def fft_planes(
    re: jax.Array,
    im: Optional[jax.Array],
    axis: int,
    inverse: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Unscaled planar DFT along ``axis``; ``im=None`` means real input."""
    axis = axis % re.ndim
    last = re.ndim - 1
    if axis != last:
        re = jnp.moveaxis(re, axis, last)
        im = jnp.moveaxis(im, axis, last) if im is not None else None
    re, im = _fft_last(re, im, inverse)
    if axis != last:
        re = jnp.moveaxis(re, last, axis)
        im = jnp.moveaxis(im, last, axis)
    return re, im


def scale_factor(lengths: Sequence[int], norm: Optional[str], inverse: bool) -> float:
    """Composite normalization over the transformed axis lengths."""
    total = 1.0
    for n in lengths:
        total *= float(n)
    if norm in (None, "backward"):
        return 1.0 / total if inverse else 1.0
    if norm == "ortho":
        return total ** -0.5
    if norm == "forward":
        return 1.0 if inverse else 1.0 / total
    raise ValueError(f'norm must be None, "ortho", "backward" or "forward", got {norm!r}')


def fftn_planes(
    re: jax.Array,
    im: Optional[jax.Array],
    axes: Sequence[int],
    inverse: bool,
    norm: Optional[str],
) -> Tuple[jax.Array, jax.Array]:
    """Planar N-D DFT over ``axes`` with numpy norm semantics applied."""
    for ax in axes:
        re, im = fft_planes(re, im, ax, inverse)
    s = scale_factor([re.shape[a] for a in axes], norm, inverse)
    if s != 1.0:
        re, im = re * re.dtype.type(s), im * im.dtype.type(s)
    return re, im


# ----------------------------------------------------------------------
# interleaved-minor 3-D real FFT (r5).  The r4 roofline showed the planar
# Karatsuba path schedules 43.1 GB for a 512^3 transform (6.7x the 48 B/el
# minimal model): every DFT stage was 3 dots + combines + a twiddle pass.
# This path stores the complex pair INSIDE the minor dim — z[..., 2k+c] —
# so one real matmul against the 2x2-block DFT matrix IS the whole stage:
#
#   pass Z   x (n0,n1,n2) @ Wr(n2, 2m2)          -> (n0, n1, 2m2)
#   T1       re-pair transpose                   -> (m2, n1, 2n0)
#   pass X   @ W2(2n0, 2n0)                      -> (m2, n1, 2k0)
#   T2       swap middle/minor pairs             -> (m2, k0, 2n1)
#   pass Y   @ W2re / @ W2im (two dots)          -> re, im (m2, k0, k1)
#   final    rotate to (k0, k1, m2) + Hermitian upper half (flip/concat)
#
# Measured on the bench v5e at 512^3 f32: 16.7 GB scheduled (vs 43.1),
# 34.6 ms (vs 65.4) — and the 2x2-block form never materializes a
# trailing dim of 2 (TPU tiling pads minor dims to 128 lanes: a (...,2)
# tensor occupies 64x its logical bytes; round-A experiments died on it).
# Matmul precision: HIGH (compensated bf16x3, ~2.5e-5 relative at 512^3)
# unless HEAT_TPU_FFT_PRECISION overrides — the 6-pass HIGHEST policy
# doubles MXU time for accuracy below the truncation any consumer of a
# single-precision transform already accepts.
# ----------------------------------------------------------------------
@_byte_lru
def _w2_full(n: int, inverse: bool, dtype: str):
    """(2n, 2n) interleaved real form of the complex DFT matrix."""
    wre, wim = _dft_w(n, inverse, "float64")[:2]
    W = np.zeros((n, 2, n, 2), np.float64)
    W[:, 0, :, 0] = wre
    W[:, 1, :, 0] = -wim
    W[:, 0, :, 1] = wim
    W[:, 1, :, 1] = wre
    return np.asarray(W.reshape(2 * n, 2 * n), dtype)


@_byte_lru
def _w2_real_in(n: int, m: int, dtype: str):
    """(n, 2m) real-input DFT matrix truncated at the Nyquist bin."""
    wre, wim = _dft_w(n, False, "float64")[:2]
    W = np.stack([wre[:, :m], wim[:, :m]], axis=-1)  # (n, m, 2)
    return np.asarray(W.reshape(n, 2 * m), dtype)


@_byte_lru
def _w2_split(n: int, dtype: str, inverse: bool = False):
    """(2n, n) re and im column blocks of the full interleaved matrix."""
    W = _w2_full(n, inverse, dtype)
    return (
        np.ascontiguousarray(W[:, 0::2]),
        np.ascontiguousarray(W[:, 1::2]),
    )


@_byte_lru
def _w2_row_split(n: int, dtype: str, inverse: bool = False):
    """(n, 2n) row blocks applying the DFT to a SEPARATE re / im plane:
    out_interleaved = re @ rows_re + im @ rows_im — the plane pair enters
    the interleaved representation through the first dot, never through a
    materialized (..., 2) stack (the tiling trap)."""
    W = _w2_full(n, inverse, dtype)
    return (
        np.ascontiguousarray(W[0::2, :]),
        np.ascontiguousarray(W[1::2, :]),
    )


def _interleaved_precision():
    from ..core._env import precision_from_env

    return precision_from_env("HEAT_TPU_FFT_PRECISION", "high")


def _revax(a: jax.Array, ax: int) -> jax.Array:
    """Index map i -> (-i) mod n along ``ax``."""
    return jnp.concatenate(
        [
            jax.lax.slice_in_dim(a, 0, 1, axis=ax),
            jnp.flip(jax.lax.slice_in_dim(a, 1, a.shape[ax], axis=ax), ax),
        ],
        ax,
    )


def hermitian_upper(p: jax.Array, rows: int) -> jax.Array:
    """Upper-half mirror of a leading-axis half spectrum: rows 1..rows of
    ``p`` evaluated at ``p[n0-k0, (n1-k1)%n1, (n2-k2)%n2]`` — one roll +
    one multi-axis ``lax.rev`` (rev = roll o flip; the chained
    revax/concat formulation measured 1.8x slower on the bench chip).
    Negate the result for the imaginary plane.  Shared by the
    interleaved engine and the leading engine's XLA extension fallback."""
    u = p[1 : rows + 1]
    return jax.lax.rev(jnp.roll(u, (-1, -1), (1, 2)), (0, 1, 2))


def _mm_merged(a: jax.Array, w, prec) -> jax.Array:
    """One matmul along the merged minor dim (the whole DFT stage)."""
    return jax.lax.dot_general(
        a.reshape(-1, a.shape[-1]), jnp.asarray(w), (((1,), (0,)), ((), ())),
        precision=prec,
    ).reshape(*a.shape[:-1], w.shape[1])


def _mid_and_exit(z, n0: int, n1: int, inverse: bool, dt: str, prec):
    """Shared stage-X / stage-Y / exit pipeline of both interleaved
    engines: z (lead, n1, 2n0) -> re, im planes (k0, k1, lead)."""
    lead = int(z.shape[0])
    z = _mm_merged(z, _w2_full(n0, inverse, dt), prec)  # (lead, n1, 2k0)
    z = z.reshape(lead, n1, n0, 2).transpose(0, 2, 1, 3).reshape(lead, n0, 2 * n1)
    wre, wim = _w2_split(n1, dt, inverse)
    re = _mm_merged(z, wre, prec).transpose(1, 2, 0)  # (k0, k1, lead)
    im = _mm_merged(z, wim, prec).transpose(1, 2, 0)
    return re, im


def _rfft3_half(x: jax.Array, norm) -> Tuple[jax.Array, jax.Array]:
    """Half spectrum (k0, k1, n2//2+1) of a real (n0, n1, n2) array —
    the shared core of fftn (extension follows) and rfftn (this IS the
    result).  Scaling commutes with the linear Hermitian extension, so
    it is applied here once."""
    n0, n1, n2 = (int(s) for s in x.shape)
    m2 = n2 // 2 + 1
    dt = str(x.dtype)
    prec = _interleaved_precision()
    z = _mm_merged(x, _w2_real_in(n2, m2, dt), prec)  # (n0, n1, 2m2)
    z = z.reshape(n0, n1, m2, 2).transpose(2, 1, 0, 3).reshape(m2, n1, 2 * n0)
    re, im = _mid_and_exit(z, n0, n1, False, dt, prec)  # (k0, k1, m2)
    return _scaled(re, im, scale_factor([n0, n1, n2], norm, False))


def _rfft3_interleaved(x: jax.Array, norm) -> Tuple[jax.Array, jax.Array]:
    """Full 3-D spectrum of a real (n0, n1, n2) array, all axes.

    Unlike :func:`_rfft3_half` (numpy rfftn halves the LAST axis), the
    full transform may halve ANY axis — halving axis 0 lets the exit
    dots land the final (k0, k1, k2) orientation directly (no rotate
    transpose) and turns the Hermitian extension into a LEADING-axis
    slab concat.  Measured on the bench chip at 512^3: 27.6 ms vs the
    shared-core-then-extend formulation's 30.5 (13.5 GB scheduled vs
    16.7); a variant absorbing the k2 reversal into extra rev-column
    exit dots measured 28.8 — the extra MXU passes cost more than the
    saved relayout."""
    n0, n1, n2 = (int(s) for s in x.shape)
    m0 = n0 // 2 + 1
    dt = str(x.dtype)
    prec = _interleaved_precision()
    W = jnp.asarray(_w2_real_in(n0, m0, dt))
    z = jax.lax.dot_general(x, W, (((0,), (0,)), ((), ())), precision=prec)
    z = z.reshape(n1, n2, m0, 2).transpose(2, 1, 0, 3).reshape(m0, n2, 2 * n1)
    z = _mm_merged(z, _w2_full(n1, False, dt), prec)  # (m0, n2, 2k1)
    z = z.reshape(m0, n2, n1, 2).transpose(0, 2, 1, 3).reshape(m0, n1, 2 * n2)
    wre, wim = _w2_split(n2, dt)
    re_lo = _mm_merged(z, wre, prec)  # (m0, k1, k2)
    im_lo = _mm_merged(z, wim, prec)

    def upper(p):
        return hermitian_upper(p, n0 - m0)

    re = jnp.concatenate([re_lo, upper(re_lo)], 0)
    im = jnp.concatenate([im_lo, -upper(im_lo)], 0)
    return _scaled(re, im, scale_factor([n0, n1, n2], norm, False))


def rfft3_half_interleaved(x: jax.Array, norm) -> Tuple[jax.Array, jax.Array]:
    """numpy ``rfftn`` semantics for 3-D real input, all axes: the
    shared half-spectrum core (:func:`_rfft3_half`) — rfftn stops where
    fftn's Hermitian extension would begin, so it is strictly cheaper."""
    return _rfft3_half(x, norm)


@_byte_lru
def _w_irfft_exit(m_used: int, n_out: int, dtype: str):
    """(2*m_used, n_out) c2r exit matrix: the Hermitian extension IS the
    matrix.  out[x] = sum_k w_k (re_k cos(2pi k x / n) - im_k sin(...))
    with w_k = 2 for interior bins (each conjugate pair contributes
    twice) and 1 for DC and (even n) Nyquist; the sin rows are zero at
    DC/Nyquist, reproducing numpy's c2r indifference to those bins'
    imaginary parts.  Unscaled (norm handled by scale_factor)."""
    k = np.arange(m_used, dtype=np.float64)
    x = np.arange(n_out, dtype=np.float64)
    ang = 2.0 * np.pi * np.outer(k, x) / n_out
    w = np.full(m_used, 2.0)
    w[0] = 1.0
    if n_out % 2 == 0 and m_used == n_out // 2 + 1:
        w[-1] = 1.0
    W = np.zeros((m_used, 2, n_out), np.float64)
    W[:, 0, :] = w[:, None] * np.cos(ang)
    W[:, 1, :] = -w[:, None] * np.sin(ang)
    return np.asarray(W.reshape(2 * m_used, n_out), dtype)


def irfft3_interleaved(
    re: jax.Array, im: jax.Array, n_out: int, norm
) -> jax.Array:
    """numpy ``irfftn`` semantics: half spectrum (n0, n1, m2) -> real
    (n0, n1, n_out).

    numpy's own composition order (inverse transforms over axes 0, 1
    FIRST — on the thin half spectrum, half the traffic of extending
    first — then the 1-D c2r along axis 2), with the Hermitian extension
    folded into the exit MATRIX (`_w_irfft_exit`): no extension pass, no
    final rotate, and the real-only output falls out of one dot."""
    n0, n1, m2 = (int(s) for s in re.shape)
    dt = str(re.dtype)
    prec = _interleaved_precision()
    m_used = n_out // 2 + 1
    re, im = _fit(re, im, 2, m_used)
    # axis-0 inverse: entry over the minor after a thin pre-transpose
    reT = re.transpose(1, 2, 0)  # (n1, mu, n0)
    imT = im.transpose(1, 2, 0)
    rrow, irow = _w2_row_split(n0, dt, True)
    z = _mm_merged(reT, rrow, prec) + _mm_merged(imT, irow, prec)  # (n1, mu, 2k0)
    z = z.reshape(n1, m_used, n0, 2).transpose(2, 1, 0, 3).reshape(n0, m_used, 2 * n1)
    z = _mm_merged(z, _w2_full(n1, True, dt), prec)  # (k0, mu, 2k1)
    z = z.reshape(n0, m_used, n1, 2).transpose(0, 2, 1, 3).reshape(n0, n1, 2 * m_used)
    out = _mm_merged(z, _w_irfft_exit(m_used, n_out, dt), prec)  # (k0, k1, n_out)
    return _scaled(out, None, scale_factor([n0, n1, n_out], norm, True))[0]


def cfft3_interleaved(
    re: jax.Array, im: jax.Array, inverse: bool, norm
) -> Tuple[jax.Array, jax.Array]:
    """Full 3-D transform of a COMPLEX (re, im) plane pair, all axes.

    Same engine as :func:`_rfft3_interleaved` without the Hermitian
    half-spectrum: the planes enter the interleaved representation
    through the first dot's row-split matrices and leave it through the
    last dot's column-split matrices, so no (..., 2) tensor ever
    materializes."""
    n0, n1, n2 = (int(s) for s in re.shape)
    dt = str(re.dtype)
    prec = _interleaved_precision()

    rrow, irow = _w2_row_split(n2, dt, inverse)
    z = _mm_merged(re, rrow, prec) + _mm_merged(im, irow, prec)  # (n0, n1, 2k2)
    z = z.reshape(n0, n1, n2, 2).transpose(2, 1, 0, 3).reshape(n2, n1, 2 * n0)
    re_o, im_o = _mid_and_exit(z, n0, n1, inverse, dt, prec)  # (k0, k1, k2)
    return _scaled(re_o, im_o, scale_factor([n0, n1, n2], norm, inverse))


# ----------------------------------------------------------------------
# 2-D variants of the same engine (entry dot -> one re-pair transpose ->
# exit dots; extension/c2r folded like the 3-D paths)
# ----------------------------------------------------------------------
def cfft2_interleaved(re, im, inverse: bool, norm):
    """Full 2-D transform of a complex plane pair, both axes."""
    n0, n1 = (int(s) for s in re.shape)
    dt = str(re.dtype)
    prec = _interleaved_precision()
    reT, imT = re.T, im.T  # (n1, n0): entry over axis 0
    rrow, irow = _w2_row_split(n0, dt, inverse)
    z = _mm_merged(reT, rrow, prec) + _mm_merged(imT, irow, prec)  # (n1, 2k0)
    z = z.reshape(n1, n0, 2).transpose(1, 0, 2).reshape(n0, 2 * n1)
    wre, wim = _w2_split(n1, dt, inverse)
    re_o = _mm_merged(z, wre, prec)  # (k0, k1)
    im_o = _mm_merged(z, wim, prec)
    return _scaled(re_o, im_o, scale_factor([n0, n1], norm, inverse))


def rfft2_half_interleaved(x, norm):
    """numpy ``rfft2``: real (n0, n1) -> (k0, n1//2+1)."""
    n0, n1 = (int(s) for s in x.shape)
    m1 = n1 // 2 + 1
    dt = str(x.dtype)
    prec = _interleaved_precision()
    z = _mm_merged(x, _w2_real_in(n1, m1, dt), prec)  # (n0, 2m1)
    z = z.reshape(n0, m1, 2).transpose(1, 0, 2).reshape(m1, 2 * n0)
    wre, wim = _w2_split(n0, dt)
    re = _mm_merged(z, wre, prec).T  # (k0, m1)
    im = _mm_merged(z, wim, prec).T
    return _scaled(re, im, scale_factor([n0, n1], norm, False))


def rfft2_full_interleaved(x, norm):
    """Full 2-D spectrum of a real array: half + Hermitian extension
    along the minor axis (full[x, k] = conj(full[rev x, n1-k]))."""
    n0, n1 = (int(s) for s in x.shape)
    m1 = n1 // 2 + 1
    re_lo, im_lo = rfft2_half_interleaved(x, norm)

    def upper(p):
        u = p[:, 1 : n1 - m1 + 1]
        return jax.lax.rev(jnp.roll(u, -1, 0), (0, 1))

    re = jnp.concatenate([re_lo, upper(re_lo)], 1)
    im = jnp.concatenate([im_lo, -upper(im_lo)], 1)
    return re, im


def irfft2_interleaved(re, im, n_out: int, norm):
    """numpy ``irfft2``: half spectrum (n0, m1) -> real (n0, n_out),
    numpy's inverse-then-c2r order with the c2r exit matrix."""
    n0, m1 = (int(s) for s in re.shape)
    dt = str(re.dtype)
    prec = _interleaved_precision()
    m_used = n_out // 2 + 1
    re, im = _fit(re, im, 1, m_used)
    reT, imT = re.T, im.T  # (mu, n0): entry over axis 0
    rrow, irow = _w2_row_split(n0, dt, True)
    z = _mm_merged(reT, rrow, prec) + _mm_merged(imT, irow, prec)  # (mu, 2k0)
    z = z.reshape(m_used, n0, 2).transpose(1, 0, 2).reshape(n0, 2 * m_used)
    out = _mm_merged(z, _w_irfft_exit(m_used, n_out, dt), prec)  # (k0, n_out)
    return _scaled(out, None, scale_factor([n0, n_out], norm, True))[0]


def _interleaved_eligible(re: jax.Array, axes) -> bool:
    if os.environ.get("HEAT_TPU_FFT_INTERLEAVED", "1") != "1":
        return False
    nd = re.ndim
    # every engine below builds its weights from a dtype string, so f64
    # rides the same dots (native on CPU/GPU, hi/lo split in _leading on
    # TPU); other dtypes keep the per-axis fallback
    return (
        nd in (2, 3)
        and len(axes) == nd
        and re.dtype in (jnp.float32, jnp.float64)
        and sorted(a % nd for a in axes) == list(range(nd))
        and all(int(s) >= 2 for s in re.shape)
    )


def real_fftn(re: jax.Array, axes: Sequence[int], norm) -> Tuple[jax.Array, jax.Array]:
    """Full N-D FFT of a REAL array via half-spectrum + Hermitian extension.

    A real input's spectrum obeys X[k] = conj(X[-k]) over the transformed
    axes, so only n//2+1 bins of the last axis are computed through the
    remaining axes (~40% less MXU work for 3-D) and the upper half is a
    conjugated reverse-gather — one bandwidth pass.  The 3-D all-axes f32
    case takes the interleaved one-dot-per-stage path above (2.6x fewer
    scheduled bytes, measured; axis order is irrelevant for a separable
    full-length transform); the 2-D all-axes case its two-stage variant."""
    if _interleaved_eligible(re, axes):
        from . import _leading

        if _leading.leading_eligible(re, axes, False):
            if re.ndim == 3:
                return _leading.rfft3_leading(re, norm)
            return _leading.rfft2_leading(re, norm)
        if re.ndim == 3:
            return _rfft3_interleaved(re, norm)
        return rfft2_full_interleaved(re, norm)
    axes = [a % re.ndim for a in axes]
    al = axes[-1]
    n = re.shape[al]
    m = n // 2 + 1
    fre, fim = fft_planes(re, None, al, False)
    sl = tuple(slice(0, m) if d == al else slice(None) for d in range(re.ndim))
    fre, fim = fre[sl], fim[sl]
    for ax in axes[:-1]:
        fre, fim = fft_planes(fre, fim, ax, False)
    # upper half along the last axis: X[.., k] = conj(X[rev(..), n-k])
    src_last = np.asarray(n - np.arange(m, n))  # in [1, n-m]
    sub_re = jnp.take(fre, src_last, axis=al)
    sub_im = jnp.take(fim, src_last, axis=al)
    for ax in axes[:-1]:
        length = fre.shape[ax]
        rev = np.concatenate([[0], np.arange(length - 1, 0, -1)])
        sub_re = jnp.take(sub_re, rev, axis=ax)
        sub_im = jnp.take(sub_im, rev, axis=ax)
    full_re = jnp.concatenate([fre, sub_re], axis=al)
    full_im = jnp.concatenate([fim, -sub_im], axis=al)
    lengths = [re.shape[a] for a in axes]
    return _scaled(full_re, full_im, scale_factor(lengths, norm, False))


# ----------------------------------------------------------------------
# numpy-semantics 1-D ops on planes (fitting, real/Hermitian kinds, norms)
# ----------------------------------------------------------------------
def _fit(re, im, axis: int, n: int):
    """Truncate / zero-pad planes along ``axis`` to length ``n`` (numpy's
    pre-transform ``n`` semantics)."""
    axis = axis % re.ndim
    cur = re.shape[axis]
    if n == cur:
        return re, im
    if n < cur:
        sl = tuple(slice(0, n) if d == axis else slice(None) for d in range(re.ndim))
        return re[sl], None if im is None else im[sl]
    widths = [(0, n - cur) if d == axis else (0, 0) for d in range(re.ndim)]
    return jnp.pad(re, widths), None if im is None else jnp.pad(im, widths)


def _scaled(re, im, s: float):
    if s == 1.0:
        return re, im
    return re * re.dtype.type(s), None if im is None else im * im.dtype.type(s)


def _take(plane, axis: int, idx):
    return jnp.take(plane, idx, axis=axis)


def _hermitian_extend(re, im, axis: int, n_out: int):
    """Full-length spectrum from its first ``n_out//2+1`` bins.

    b[k] = a[k] for k < m, b[k] = conj(a[n_out-k]) above — numpy's implicit
    extension in irfft/hfft."""
    axis = axis % re.ndim
    m = n_out // 2 + 1
    re, im = _fit(re, im, axis, m)
    if im is None:
        im = jnp.zeros_like(re)
    ext_idx = jnp.arange(1, n_out - m + 1)[::-1]
    re_full = jnp.concatenate([re, _take(re, axis, ext_idx)], axis=axis)
    im_full = jnp.concatenate([im, -_take(im, axis, ext_idx)], axis=axis)
    return re_full, im_full


def fft1(re, im, axis: int, n: Optional[int], norm, inverse: bool):
    """numpy fft/ifft semantics on planes (complex in, complex out)."""
    n = n if n is not None else re.shape[axis]
    re, im = _fit(re, im, axis, n)
    re, im = fft_planes(re, im, axis, inverse)
    return _scaled(re, im, scale_factor([n], norm, inverse))


def rfft1(re, axis: int, n: Optional[int], norm):
    """numpy rfft: real input, spectrum truncated at Nyquist."""
    axis = axis % re.ndim
    n = n if n is not None else re.shape[axis]
    re, _ = _fit(re, None, axis, n)
    fre, fim = fft_planes(re, None, axis, False)
    m = n // 2 + 1
    sl = tuple(slice(0, m) if d == axis else slice(None) for d in range(fre.ndim))
    return _scaled(fre[sl], fim[sl], scale_factor([n], norm, False))


def irfft1(re, im, axis: int, n: Optional[int], norm):
    """numpy irfft: Hermitian-extend, inverse transform, real output."""
    n_out = n if n is not None else 2 * (re.shape[axis] - 1)
    re_f, im_f = _hermitian_extend(re, im, axis, n_out)
    ore, _ = fft_planes(re_f, im_f, axis, True)
    s = scale_factor([n_out], norm, True)
    return ore * ore.dtype.type(s) if s != 1.0 else ore


def hfft1(re, im, axis: int, n: Optional[int], norm):
    """numpy hfft: forward transform of the Hermitian-extended signal,
    real output, forward-family norm scaling (None->1, ortho->1/sqrt,
    forward->1/n — verified against np.fft.hfft)."""
    n_out = n if n is not None else 2 * (re.shape[axis] - 1)
    re_f, im_f = _hermitian_extend(re, im, axis, n_out)
    ore, _ = fft_planes(re_f, im_f, axis, False)
    s = scale_factor([n_out], norm, False)
    return ore * ore.dtype.type(s) if s != 1.0 else ore


def ihfft1(re, axis: int, n: Optional[int], norm):
    """numpy ihfft == conj(rfft)/n with inverse-family norm scaling."""
    n_in = n if n is not None else re.shape[axis]
    fre, fim = rfft1(re, axis, n_in, None)
    fre, fim = _scaled(fre, fim, scale_factor([n_in], norm, True))
    return fre, -fim
