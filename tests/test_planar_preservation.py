"""Plane-preservation guarantees (VERDICT r3 #7): elementwise chains on
planar complex arrays stay on the planes — fftn(x) * H -> ifftn never
materializes a complex array — and materialization, when an op needs it,
happens on the mesh.

The planar engine is selected via HEAT_TPU_PLANAR=1; materialization is
trapped by poisoning DNDarray._DNDarray__materialize_planar for the
duration.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core.dndarray import DNDarray


@pytest.fixture()
def planar_mode():
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        yield
    finally:
        del os.environ["HEAT_TPU_PLANAR"]


class _NoMaterialize:
    """Poison planar materialization so any host/complex fallback fails."""

    def __enter__(self):
        self._orig = DNDarray._DNDarray__materialize_planar

        def boom(self_arr):
            raise AssertionError("planar array was materialized mid-chain")

        DNDarray._DNDarray__materialize_planar = boom
        return self

    def __exit__(self, *exc):
        DNDarray._DNDarray__materialize_planar = self._orig
        return False


def test_fftn_filter_ifftn_stays_on_mesh(planar_mode):
    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((16, 8)).astype(np.float32)
    h_np = rng.standard_normal((16, 8)).astype(np.float32)
    x = ht.array(x_np, split=0)
    h = ht.array(h_np, split=0)
    with _NoMaterialize():
        spec = ht.fft.fftn(x)
        assert spec._planar is not None
        filt = spec * h  # planar * real-array fast path
        assert filt._planar is not None
        back = ht.fft.ifftn(filt)
        assert back._planar is not None
    want = np.fft.ifftn(np.fft.fftn(x_np) * h_np)
    np.testing.assert_allclose(np.asarray(back.numpy()), want, atol=1e-4)


def test_planar_binary_table(planar_mode):
    rng = np.random.default_rng(1)
    a_np = rng.standard_normal((12, 6)).astype(np.float32)
    b_np = rng.standard_normal((12, 6)).astype(np.float32)
    a = ht.fft.fft(ht.array(a_np, split=0), axis=0)
    b = ht.fft.fft(ht.array(b_np, split=0), axis=0)
    fa = np.fft.fft(a_np, axis=0)
    fb = np.fft.fft(b_np, axis=0)
    cases = [
        (a + b, fa + fb),
        (a - b, fa - fb),
        (a * b, fa * fb),
        (a / b, fa / fb),
        (a + 2.0, fa + 2.0),
        (a * (1.5 - 0.5j), fa * (1.5 - 0.5j)),
        (a / 2.0, fa / 2.0),
        (3.0 * a, 3.0 * fa),
        (-a, -fa),
    ]
    with _NoMaterialize():
        for got, _ in cases:
            assert got._planar is not None, "plane path skipped"
    for got, want in cases:
        np.testing.assert_allclose(np.asarray(got.numpy()), want, atol=1e-3)


def test_scalar_complex_div(planar_mode):
    rng = np.random.default_rng(2)
    a_np = rng.standard_normal(32).astype(np.float64)
    a = ht.fft.fft(ht.array(a_np, split=0))
    fa = np.fft.fft(a_np)
    with _NoMaterialize():
        got = a / (2.0 + 1.0j)
        assert got._planar is not None
    np.testing.assert_allclose(np.asarray(got.numpy()), fa / (2.0 + 1.0j), atol=1e-10)


def test_materialization_stays_on_mesh(planar_mode):
    """An op without a plane fast path materializes the complex array ON
    the mesh (``jax.lax.complex`` of the two planes): same devices, same
    sharding as the planes — never a host or CPU-backend copy."""
    x_np = np.random.default_rng(3).standard_normal((16, 8)).astype(np.float32)
    a = ht.fft.fft(ht.array(x_np, split=0), axis=1)
    assert a._planar is not None
    re, _ = a._planar
    comp = a.larray_padded
    assert comp.dtype == jnp.complex64
    assert comp.devices() == re.devices()
    assert comp.sharding.is_equivalent_to(re.sharding, comp.ndim)
    np.testing.assert_allclose(
        complex(ht.sum(a).item()), np.fft.fft(x_np, axis=1).sum(), rtol=1e-4, atol=1e-4
    )
