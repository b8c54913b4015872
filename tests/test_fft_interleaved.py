"""Interleaved-minor 3-D FFT paths (r5 headline): the one-dot-per-stage
real transform, the complex-input engine behind fftn->filter->ifftn
chains, and the conj-trick real ifftn — all against numpy across shapes
and norms.  The representation invariant (no materialized (..., 2)
tensor, no index-grid gathers) is what keeps the 512^3 transform at
16.7 GB scheduled instead of 43.1 (docs/fft_roofline.md).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.fft import _planar as P

SHAPES = [(32, 16, 24), (17, 9, 13), (8, 8, 8), (2, 3, 2)]
NORMS = [None, "ortho", "forward"]


def _np_norm(norm):
    return norm


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("norm", NORMS)
def test_rfft3_matches_numpy(shape, norm):
    rng = np.random.default_rng(1)
    x = rng.standard_normal(shape).astype(np.float32)
    re, im = jax.jit(lambda v: P.real_fftn(v, [0, 1, 2], norm))(jnp.asarray(x))
    got = np.asarray(re) + 1j * np.asarray(im)
    want = np.fft.fftn(x, norm=_np_norm(norm))
    rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert rel < 5e-5, (shape, norm, rel)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("inverse", [False, True])
def test_cfft3_matches_numpy(shape, inverse):
    rng = np.random.default_rng(2)
    x = rng.standard_normal(shape).astype(np.float32)
    y = rng.standard_normal(shape).astype(np.float32)
    re, im = jax.jit(lambda a, b: P.cfft3_interleaved(a, b, inverse, None))(
        jnp.asarray(x), jnp.asarray(y)
    )
    got = np.asarray(re) + 1j * np.asarray(im)
    fn = np.fft.ifftn if inverse else np.fft.fftn
    want = fn(x + 1j * y)
    rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    assert rel < 5e-5, (shape, inverse, rel)


def test_fftn_ifftn_round_trip_planar():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((24, 12, 18)).astype(np.float32)
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        f = ht.fft.fftn(ht.array(x))
        assert f._planar is not None
        b = ht.fft.ifftn(f)  # complex planar input -> cfft3 engine
        got = np.asarray(b.numpy())
        np.testing.assert_allclose(got.real, x, atol=6e-4)
        assert np.abs(got.imag).max() < 6e-4
        # real ifftn (conj trick)
        bi = ht.fft.ifftn(ht.array(x))
        want_bi = np.fft.ifftn(x)
        np.testing.assert_allclose(
            np.asarray(bi.numpy()), want_bi,
            atol=1e-4 * max(np.abs(want_bi).max(), 1e-3),
        )
    finally:
        os.environ.pop("HEAT_TPU_PLANAR", None)


def test_norms_compose_through_round_trip():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((16, 10, 14)).astype(np.float32)
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        for norm in NORMS:
            f = ht.fft.fftn(ht.array(x), norm=norm)
            b = ht.fft.ifftn(f, norm=norm)
            np.testing.assert_allclose(np.asarray(b.numpy()).real, x, atol=6e-4)
    finally:
        os.environ.pop("HEAT_TPU_PLANAR", None)


@pytest.mark.parametrize("shape", [(16, 12, 20), (9, 7, 13)])
def test_rfftn_irfftn_interleaved(shape):
    rng = np.random.default_rng(7)
    x = rng.standard_normal(shape).astype(np.float32)
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        f = ht.fft.rfftn(ht.array(x))
        want = np.fft.rfftn(x)
        sc = np.abs(want).max()
        np.testing.assert_allclose(np.asarray(f.numpy()), want, atol=1e-4 * sc, rtol=1e-3)
        b = ht.fft.irfftn(f)
        np.testing.assert_allclose(np.asarray(b.numpy()), np.fft.irfftn(want), atol=6e-4)
        # ARBITRARY (non-Hermitian-consistent) half input must still match
        # numpy's ifft-then-extend order (the engine extends first with the
        # rev-compensated rule, which is algebraically identical)
        m2 = shape[2] // 2 + 1
        carr = (
            rng.standard_normal((shape[0], shape[1], m2))
            + 1j * rng.standard_normal((shape[0], shape[1], m2))
        ).astype(np.complex64)
        got = ht.fft.irfftn(ht.array(carr))
        want2 = np.fft.irfftn(carr)
        np.testing.assert_allclose(
            np.asarray(got.numpy()), want2,
            atol=2e-5 * max(1.0, np.abs(carr).max()), rtol=1e-3,
        )
    finally:
        os.environ.pop("HEAT_TPU_PLANAR", None)


@pytest.mark.parametrize("shape", [(24, 18), (13, 9), (8, 8)])
def test_2d_engine_all_kinds(shape):
    rng = np.random.default_rng(11)
    x = rng.standard_normal(shape).astype(np.float32)
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        f = ht.fft.fft2(ht.array(x))
        want = np.fft.fft2(x)
        np.testing.assert_allclose(
            np.asarray(f.numpy()), want, atol=1e-4 * np.abs(want).max(), rtol=1e-3
        )
        b = ht.fft.ifft2(f)
        np.testing.assert_allclose(np.asarray(b.numpy()).real, x, atol=6e-4)
        rf = ht.fft.rfft2(ht.array(x))
        wrf = np.fft.rfft2(x)
        np.testing.assert_allclose(
            np.asarray(rf.numpy()), wrf, atol=1e-4 * np.abs(wrf).max(), rtol=1e-3
        )
        rb = ht.fft.irfft2(rf)
        np.testing.assert_allclose(np.asarray(rb.numpy()), np.fft.irfft2(wrf), atol=6e-4)
        m1 = shape[1] // 2 + 1
        carr = (
            rng.standard_normal((shape[0], m1))
            + 1j * rng.standard_normal((shape[0], m1))
        ).astype(np.complex64)
        got = ht.fft.irfft2(ht.array(carr))
        np.testing.assert_allclose(
            np.asarray(got.numpy()), np.fft.irfft2(carr),
            atol=3e-5 * max(1.0, np.abs(carr).max()), rtol=1e-3,
        )
    finally:
        os.environ.pop("HEAT_TPU_PLANAR", None)


@pytest.mark.parametrize("shape", [(12, 10, 9), (8, 6)])
@pytest.mark.parametrize("norm", NORMS)
def test_hfftn_ihfftn_engine(shape, norm):
    rng = np.random.default_rng(13)
    x = rng.standard_normal(shape).astype(np.float32)
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        got = ht.fft.ihfftn(ht.array(x), norm=norm)
        want = np.fft.ihfft(x, axis=-1, norm=norm)
        for ax in range(len(shape) - 1):
            want = np.fft.ifft(want, axis=ax, norm=norm)
        np.testing.assert_allclose(np.asarray(got.numpy()), want, atol=2e-5, rtol=1e-3)

        m = shape[-1]
        carr = (
            rng.standard_normal(shape[:-1] + (m,))
            + 1j * rng.standard_normal(shape[:-1] + (m,))
        ).astype(np.complex64)
        goth = ht.fft.hfftn(ht.array(carr), norm=norm)
        wanth = carr.copy()
        for ax in range(len(shape) - 1):
            wanth = np.fft.fft(wanth, axis=ax, norm=norm)
        wanth = np.fft.hfft(wanth, axis=-1, norm=norm)
        sc = max(np.abs(wanth).max(), 1.0)
        np.testing.assert_allclose(
            np.asarray(goth.numpy()), wanth, atol=2e-4 * sc, rtol=1e-3
        )
    finally:
        os.environ.pop("HEAT_TPU_PLANAR", None)


def test_env_gate_and_fallback_agree():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((12, 8, 10)).astype(np.float32)
    fast = jax.jit(lambda v: P.real_fftn(v, [0, 1, 2], None))(jnp.asarray(x))
    os.environ["HEAT_TPU_FFT_INTERLEAVED"] = "0"
    try:
        slow = jax.jit(lambda v: P.real_fftn(v, [0, 1, 2], None))(jnp.asarray(x))
    finally:
        del os.environ["HEAT_TPU_FFT_INTERLEAVED"]
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=1e-4)


def test_bad_precision_env_is_diagnostic():
    os.environ["HEAT_TPU_FFT_PRECISION"] = "hi"
    try:
        with pytest.raises(ValueError, match="HEAT_TPU_FFT_PRECISION"):
            P._interleaved_precision()
    finally:
        del os.environ["HEAT_TPU_FFT_PRECISION"]
