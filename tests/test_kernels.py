"""Pallas kernel tests (core/kernels.py) — run through the Pallas
interpreter on the virtual CPU mesh, same code path as Mosaic on TPU.
The module holds one kernel, ``gram_syrk``."""

import numpy as np

import jax.numpy as jnp


class TestSyrk:
    """gram_syrk: the one-read Gram kernel behind hsvd (r5)."""

    def test_values_with_remainder_tail(self, ht):
        from heat_tpu.core import kernels

        rng = np.random.default_rng(3)
        m = 2 * kernels._SYRK_TILE + 137  # exercises kernel + XLA tail
        x = rng.standard_normal((m, 128)).astype(np.float32)
        assert kernels.syrk_supported(m, 128, jnp.float32)
        g = np.asarray(kernels.gram_syrk(jnp.asarray(x)))
        want = x.astype(np.float64).T @ x.astype(np.float64)
        rel = np.linalg.norm(g - want) / np.linalg.norm(want)
        assert rel < 5e-5, rel  # compensated bf16x3 + Kahan accumulation
        np.testing.assert_allclose(g, g.T, rtol=1e-5, atol=1e-4)

    def test_unsupported_shapes(self, ht):
        from heat_tpu.core import kernels

        assert not kernels.syrk_supported(100, 128, jnp.float32)  # too short
        assert not kernels.syrk_supported(10000, 100, jnp.float32)  # lanes
        assert not kernels.syrk_supported(10000, 128, jnp.float64)  # dtype

    def test_hsvd_uses_it_and_matches(self, ht):
        import heat_tpu as htm
        from heat_tpu.core.linalg.svdtools import _hsvd_rank_jit

        rng = np.random.default_rng(4)
        m = 3 * 2048 + 11
        xh = rng.standard_normal((m, 64)).astype(np.float32)
        x = htm.array(xh, split=0)
        # public API on the multi-device mesh (syrk gated OFF there:
        # pallas_call is not GSPMD-partitionable)
        u, s, v, err = htm.linalg.hsvd_rank(x, 10, compute_sv=True)
        want_s = np.linalg.svd(xh, compute_uv=False)[:10]
        np.testing.assert_allclose(np.asarray(s.numpy()), want_s, rtol=1e-3)
        un = u.numpy()
        np.testing.assert_allclose(un.T @ un, np.eye(10), atol=1e-3)
        # the single-device jit WITH the kernel path matches the same truth
        u2, s2, v2, e2 = _hsvd_rank_jit(
            jnp.asarray(xh), 15, 1, 2, 10, True, "float32", syrk_ok=True
        )
        np.testing.assert_allclose(np.asarray(s2), want_s, rtol=1e-3)
