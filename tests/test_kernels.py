"""Pallas kernel tests (core/kernels.py) — run through the Pallas
interpreter on the virtual CPU mesh, same code path as Mosaic on TPU."""

import numpy as np
import pytest

import jax.numpy as jnp


def _numpy_lloyd(x, c):
    d = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    lbl = d.argmin(1)
    new = np.stack([x[lbl == j].mean(0) if (lbl == j).any() else c[j] for j in range(c.shape[0])])
    return new, d.min(1).sum()


@pytest.mark.parametrize(
    "n,f,k",
    [(1003, 16, 8), (517, 8, 5), (130, 4, 7), (999, 16, 12), (96, 128, 8), (64, 64, 2)],
)
def test_lloyd_kernel_single(ht, n, f, k):
    from heat_tpu.core import kernels

    assert kernels.lloyd_supported(f, k)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, f)).astype(np.float32)
    c = rng.standard_normal((k, f)).astype(np.float32)
    npad = -(-n // 32) * 32
    xp = np.zeros((npad, f), np.float32)
    xp[:n] = x
    new, shift, inertia = kernels._lloyd_single(jnp.asarray(xp), jnp.asarray(c), n)
    ref, ref_inertia = _numpy_lloyd(x, c)
    np.testing.assert_allclose(np.asarray(new), ref, atol=5e-5)
    np.testing.assert_allclose(float(inertia), ref_inertia, rtol=1e-4)


def test_lloyd_kernel_sharded(ht):
    from heat_tpu.core import kernels

    ht.random.seed(5)
    x = ht.random.randn(1003, 16, split=0)  # uneven over 8 devices
    rng = np.random.default_rng(1)
    c = rng.standard_normal((8, 16)).astype(np.float32)
    new, shift, inertia = kernels.lloyd_update(x, jnp.asarray(c))
    ref, ref_inertia = _numpy_lloyd(x.numpy().astype(np.float32), c)
    np.testing.assert_allclose(np.asarray(new), ref, atol=5e-5)
    np.testing.assert_allclose(float(inertia), ref_inertia, rtol=1e-4)


def test_lloyd_unsupported_shapes(ht):
    from heat_tpu.core import kernels

    assert not kernels.lloyd_supported(17, 8)  # f does not divide 128
    assert not kernels.lloyd_supported(4, 30)  # packed space too wide
    assert not kernels.lloyd_supported(0, 8)


def test_kmeans_kernel_flag_end_to_end(ht, monkeypatch):
    """KMeans produces the same clustering through both step paths."""
    from heat_tpu.core import kernels

    # four separated blobs: on structureless noise no tolerance holds
    # between two arithmetics, one flipped row sends the 30 iterations
    # elsewhere.  Since PR 27 the XLA path rounds the points to bfloat16
    # (kmeans._half_d2) and the Pallas path does not, so with the same
    # clustering each center is the mean of ~125 rows rounded by up to
    # 2^-9 |x| (|x| <= 12): within 12 * 2^-9 / sqrt(125) = 2e-3
    rng = np.random.default_rng(7)
    offsets = 8.0 * np.eye(4, 16, dtype=np.float32)[rng.integers(0, 4, 500)]
    x = ht.array(rng.standard_normal((500, 16)).astype(np.float32) + offsets, split=0)
    km_xla = ht.cluster.KMeans(n_clusters=4, init="kmeans++", max_iter=30, random_state=0)
    km_xla.fit(x)
    monkeypatch.setattr(kernels, "LLOYD_KERNEL", True)
    km_pal = ht.cluster.KMeans(n_clusters=4, init="kmeans++", max_iter=30, random_state=0)
    km_pal.fit(x)
    np.testing.assert_allclose(
        km_xla.cluster_centers_.numpy(), km_pal.cluster_centers_.numpy(), atol=2e-3
    )


class TestLloydKernelProperties:
    """Property tests across the packed (f, k) space (VERDICT: the packed
    argmin/unscramble logic needs coverage across lane/slot combinations,
    including the lloyd_supported boundary)."""

    def test_supported_boundary_exhaustive(self):
        """lloyd_supported must be exactly 'f divides 128 and packed width
        r*next_pow2_widened(k) <= 512' — checked against first principles
        over the full small (f, k) grid."""
        from heat_tpu.core import kernels

        for f in list(range(1, 130)) + [256]:
            for k in range(1, 40):
                want = False
                if f > 0 and 128 % f == 0:
                    r = 128 // f
                    kp = 1
                    while kp < k:
                        kp *= 2
                    while r * kp < 128:
                        kp *= 2
                    want = r * kp <= 512
                assert kernels.lloyd_supported(f, k) == want, (f, k)

    @pytest.mark.parametrize(
        "f,k",
        [
            (128, 4),   # one point per lane row, kp == 4 (min widening)
            (128, 13),  # non-pow2 k, kp = 16
            (64, 2),    # r=2, kp widened 2 -> 64 to fill lanes
            (32, 8),    # r=4, kp widened to 32
            (16, 3),    # r=8, kp widened 4 -> 16
            (8, 9),     # r=16, kp=16: r*kp = 256 (multi-row packed space)
            (4, 16),    # r=32, kp=16: r*kp = 512 (exactly at the bound)
            (2, 2),     # r=64, minimum feature width
            (1, 4),     # r=128: scalar features
        ],
    )
    def test_packed_space_sweep(self, f, k):
        """Every lane/slot packing shape reproduces the numpy Lloyd update."""
        from heat_tpu.core import kernels

        assert kernels.lloyd_supported(f, k), (f, k)
        rng = np.random.default_rng(f * 100 + k)
        n = 517  # not a multiple of the 32-row padding quantum
        x = rng.standard_normal((n, f)).astype(np.float32)
        c = rng.standard_normal((k, f)).astype(np.float32)
        npad = -(-n // 32) * 32
        xp = np.zeros((npad, f), np.float32)
        xp[:n] = x
        new, shift, inertia = kernels._lloyd_single(jnp.asarray(xp), jnp.asarray(c), n)
        ref, ref_inertia = _numpy_lloyd(x, c)
        np.testing.assert_allclose(np.asarray(new), ref, atol=5e-5, err_msg=f"f={f} k={k}")
        np.testing.assert_allclose(float(inertia), ref_inertia, rtol=1e-4)

    def test_empty_cluster_keeps_center(self):
        """A cluster that captures no points must keep its center (the
        _postprocess where-guard), not collapse to NaN."""
        from heat_tpu.core import kernels

        x = np.zeros((64, 16), np.float32)  # every point at the origin
        c = np.stack([np.zeros(16), np.full(16, 100.0)]).astype(np.float32)
        new, shift, inertia = kernels._lloyd_single(jnp.asarray(x), jnp.asarray(c), 64)
        got = np.asarray(new)
        assert not np.isnan(got).any()
        np.testing.assert_allclose(got[1], c[1], atol=1e-6)  # empty cluster frozen
        np.testing.assert_allclose(got[0], 0.0, atol=1e-6)

    def test_padding_rows_excluded(self):
        """Padded rows beyond n_true must contribute nothing — compare a
        64-row buffer holding 40 true points against the direct 40-point
        numpy update, with garbage (not zeros) in the padding."""
        from heat_tpu.core import kernels

        rng = np.random.default_rng(9)
        n, f, k = 40, 16, 5
        x = rng.standard_normal((n, f)).astype(np.float32)
        c = rng.standard_normal((k, f)).astype(np.float32)
        xp = np.full((64, f), 1e6, np.float32)  # poison padding
        xp[:n] = x
        new, shift, inertia = kernels._lloyd_single(jnp.asarray(xp), jnp.asarray(c), n)
        ref, ref_inertia = _numpy_lloyd(x, c)
        np.testing.assert_allclose(np.asarray(new), ref, atol=5e-5)
        np.testing.assert_allclose(float(inertia), ref_inertia, rtol=1e-4)

    def test_multi_tile_grid(self):
        """n above the tile quantum exercises the multi-step grid
        accumulation path."""
        from heat_tpu.core import kernels

        rng = np.random.default_rng(10)
        n, f, k = 40000, 64, 3  # r=2 -> g=2048 rows/tile -> ~10 tiles
        x = rng.standard_normal((n, f)).astype(np.float32)
        c = rng.standard_normal((k, f)).astype(np.float32)
        npad = -(-n // 32) * 32
        xp = np.zeros((npad, f), np.float32)
        xp[:n] = x
        new, shift, inertia = kernels._lloyd_single(jnp.asarray(xp), jnp.asarray(c), n)
        ref, ref_inertia = _numpy_lloyd(x, c)
        np.testing.assert_allclose(np.asarray(new), ref, atol=5e-4)
        np.testing.assert_allclose(float(inertia), ref_inertia, rtol=1e-3)


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
