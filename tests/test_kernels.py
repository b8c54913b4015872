"""Pallas kernel tests (core/kernels.py) — run through the Pallas
interpreter on the virtual CPU mesh, same code path as Mosaic on TPU.
The module's first kernel, ``gram_syrk``, and since PR 40 its moments' body."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

WIDTHS = [128, 256, 512]
#: rows as a function of one tile's rows at the width
ROWS = {
    "two_tiles_and_137": lambda tile: 2 * tile + 137,  # kernel + XLA tail
    "one_tile": lambda tile: tile,                     # kernel alone, one grid step
    "one_row_short": lambda tile: tile - 1,            # under the gate: the XLA product
}


def _split(blk):
    hi = blk.astype(jnp.bfloat16)
    lo = (blk - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _three_products(x, rows):
    """The kernel's arithmetic in plain ``jax.numpy``, with the third
    product written out: per tile hi^T hi + hi^T lo + lo^T hi in float32,
    Kahan-summed over the tiles, the row remainder at ``HIGH``."""
    m, n = x.shape
    dims = (((0,), (0,)), ((), ()))
    dot = lambda a, b: jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    m0 = m // rows * rows
    if m0 == 0:
        return jnp.matmul(x.T, x, precision=jax.lax.Precision.HIGH)
    acc = comp = jnp.zeros((n, n), jnp.float32)
    for i in range(m0 // rows):
        hi, lo = _split(x[i * rows:(i + 1) * rows])
        y = dot(hi, hi) + dot(hi, lo) + dot(lo, hi) - comp
        t = acc + y
        comp = (t - acc) - y
        acc = t
    if m0 < m:
        acc = acc + jnp.matmul(x[m0:].T, x[m0:], precision=jax.lax.Precision.HIGH)
    return acc


class TestSyrk:
    """gram_syrk: the one-read Gram kernel behind hsvd (r5; two products a
    step on a tile of ``_SYRK_TILE_BYTES`` since PR 30)."""

    @pytest.mark.parametrize("kind", list(ROWS))
    @pytest.mark.parametrize("n", WIDTHS)
    def test_values(self, ht, n, kind):
        from heat_tpu.core import kernels

        tile = kernels._syrk_rows(n)
        assert tile * n * 4 <= kernels._SYRK_TILE_BYTES and tile % 128 == 0
        m = ROWS[kind](tile)
        assert kernels.syrk_supported(m, n, jnp.float32) == (kind != "one_row_short")
        x = np.random.default_rng(3).standard_normal((m, n)).astype(np.float32)
        g = np.asarray(kernels.gram_syrk(jnp.asarray(x)))

        # the same split with the third product taken, not transposed: bit for bit
        np.testing.assert_array_equal(g, np.asarray(_three_products(jnp.asarray(x), tile)))

        # Against float64.  What the three terms give is computed here, from
        # the same split in float64: x = hi + lo + e, |e| <= 2^-16 |x|, and
        # lo^T lo is dropped, so the diagonal reads ~3e-6 low.  On top of it a
        # float32 sum of one tile's rows walks at most sqrt(rows) * 2^-24 from
        # the exact sum (the Kahan buffer keeps the tiles from adding to it).
        want = x.astype(np.float64).T @ x.astype(np.float64)
        hi, lo = (np.asarray(p, np.float64) for p in _split(jnp.asarray(x)))
        three = hi.T @ hi + hi.T @ lo + lo.T @ hi
        rel = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(want)
        summing = np.sqrt(tile) * 2.0**-24
        assert 1e-6 < rel(three, want) < 5e-6  # the tolerance is the terms', not a guess
        if kind != "one_row_short":  # the XLA product at HIGH is plain float32 on the CPU, nearer than the terms
            assert rel(g, three) < summing, (rel(g, three), summing)
        assert rel(g, want) < rel(three, want) + summing, rel(g, want)
        np.testing.assert_allclose(g, g.T, rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("m", [2048, 3000], ids=["whole_tiles", "with_a_tail"])
    def test_about_a_shift(self, ht, m):
        """``gram_syrk(x, shift)`` (PR 39): the Gram of ``x - shift``, the
        shift taken from every tile and from the rows past the last one; a
        column that stands 50 spreads off zero is then held to its SPREAD
        (as the table lies the same three terms hold it to its mean's
        square, 2,500 times as much)."""
        from heat_tpu.core import kernels

        x = np.random.default_rng(5).standard_normal((m, 512)).astype(np.float32) + 50 * (np.arange(512) % 7 == 0).astype(np.float32)
        shift = x[:256].mean(axis=0)
        g = np.asarray(jax.jit(kernels.gram_syrk)(jnp.asarray(x), jnp.asarray(shift)))
        xc = x.astype(np.float64) - shift.astype(np.float64)
        want = xc.T @ xc
        assert np.diag(want).max() < 2 * m  # spreads of 1, wherever the columns stand
        np.testing.assert_allclose(g, want, rtol=0, atol=5e-6 * np.diag(want).max())
        plain = np.asarray(jax.jit(kernels.gram_syrk)(jnp.asarray(x)))
        np.testing.assert_allclose(plain, x.astype(np.float64).T @ x.astype(np.float64), rtol=0, atol=5e-6 * 2501 * m)

    @pytest.mark.parametrize("off_zero", [False, True], ids=["no_shift", "shift_far_off_zero"])
    @pytest.mark.parametrize("kind", ["two_tiles", "two_tiles_and_137"])
    @pytest.mark.parametrize("n", [128, 256])
    def test_moments_from_the_grams_tiles(self, ht, n, kind, off_zero):
        """``gram_syrk(x, c, y, cy)`` (PR 40): the moments' body reads each
        tile once for the Gram and for the columns' sums, their products with
        ``y - cy``, their sums of squares and ``y - cy``'s sum, the rows past
        the last tile in XLA.  Its Gram is the one without ``y``, bit for bit;
        the moments are as near float64 as `lasso._moments`' blocks are held
        (`test_the_normal_equations_stand_in_a_shifted_frame`'s tolerances).
        Off zero, every seventh column stands 50 spreads away and ``y`` 40,
        and the shift is the first rows' means; else no shift at all."""
        from heat_tpu.core import kernels

        tile = kernels._syrk_rows(n)
        m = 2 * tile + (137 if kind.endswith("137") else 0)
        rng = np.random.default_rng(11)
        x = rng.standard_normal((m, n)).astype(np.float32)
        y = (x[:, :3].sum(axis=1) + 0.5 + rng.standard_normal(m)).astype(np.float32)
        if off_zero:
            x += 50 * (np.arange(n) % 7 == 0).astype(np.float32)
            y += 40
            c, cy = x[:256].mean(axis=0), y[:256].mean()
            args = (jnp.asarray(x), jnp.asarray(c), jnp.asarray(y), jnp.asarray(cy))
            g0 = jax.jit(kernels.gram_syrk)(jnp.asarray(x), jnp.asarray(c))
        else:
            c, cy = np.zeros(n, np.float32), np.float32(0)
            args = (jnp.asarray(x), None, jnp.asarray(y))
            g0 = jax.jit(kernels.gram_syrk)(jnp.asarray(x))
        g, s1, bxy, q, sy = (np.asarray(a, np.float64) for a in jax.jit(kernels.gram_syrk)(*args))
        np.testing.assert_array_equal(g, np.asarray(g0))

        xc = x.astype(np.float64) - c
        yc = y.astype(np.float64) - np.float64(cy)
        want = xc.T @ xc
        off = ~np.eye(n, dtype=bool)
        np.testing.assert_allclose(g[off], want[off], rtol=0, atol=5e-6 * np.diag(want).max())
        np.testing.assert_allclose(q, (xc * xc).sum(axis=0), rtol=3e-7)
        np.testing.assert_allclose(s1, xc.sum(axis=0), rtol=0, atol=2e-6 * m)
        np.testing.assert_allclose(bxy, xc.T @ yc, rtol=0, atol=2e-6 * m * max(1.0, np.abs(yc).max() / 10))
        np.testing.assert_allclose(sy, yc.sum(), rtol=0, atol=2e-6 * m * max(1.0, np.abs(yc).max() / 10))

    @pytest.mark.parametrize("m", [2048, 3000], ids=["whole_tiles", "with_a_tail"])
    def test_without_y_the_kernel_is_what_it_was(self, ht, m):
        """Without ``y`` the call is the one hSVD and PR 39 made: ``gram_syrk(x)``
        and ``gram_syrk(x, c)`` are the three products of ``x`` and of ``x - c``
        bit for bit (the moments' body is a kernel of its own)."""
        from heat_tpu.core import kernels

        x = np.random.default_rng(6).standard_normal((m, 512)).astype(np.float32) + 3
        c = x[:256].mean(axis=0)
        tile = kernels._syrk_rows(512)
        np.testing.assert_array_equal(np.asarray(kernels.gram_syrk(jnp.asarray(x))),
                                      np.asarray(_three_products(jnp.asarray(x), tile)))
        np.testing.assert_array_equal(np.asarray(kernels.gram_syrk(jnp.asarray(x), jnp.asarray(c))),
                                      np.asarray(_three_products(jnp.asarray(x) - jnp.asarray(c), tile)))

    def test_unsupported_shapes(self, ht):
        from heat_tpu.core import kernels

        assert not kernels.syrk_supported(100, 128, jnp.float32)  # too short
        assert not kernels.syrk_supported(10000, 100, jnp.float32)  # lanes
        assert not kernels.syrk_supported(10000, 128, jnp.float64)  # dtype
        assert not kernels.syrk_supported(10000, 640, jnp.float32)  # wider than fits

    @pytest.mark.parametrize("n, rows", [(128, 4096), (256, 2048), (384, 1280), (512, 1024)])
    def test_tile_rows_follow_the_width(self, ht, n, rows):
        from heat_tpu.core import kernels

        assert kernels._syrk_rows(n) == rows
        assert kernels.syrk_supported(rows, n, jnp.float32)
        assert not kernels.syrk_supported(rows - 1, n, jnp.float32)

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

    def test_hsvd_rank_takes_the_kernel_at_width_128(self, ht):
        """One tile and a tail at the width the kernel serves: the traced
        program holds the ``pallas_call`` and the singular values are the
        float64 ones to the Gram's 1e-6."""
        from heat_tpu.core import kernels
        from heat_tpu.core.linalg.svdtools import _hsvd_rank_jit

        m = kernels._syrk_rows(128) + 11
        xh = (np.random.default_rng(5).standard_normal((m, 128)) * np.geomspace(1.0, 1e-2, 128)).astype(np.float32)
        args = (jnp.asarray(xh), 15, 1, 2, 10, True, "float32")
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda a: _hsvd_rank_jit(a, *args[1:], syrk_ok=True))(args[0]))
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda a: _hsvd_rank_jit(a, *args[1:], syrk_ok=False))(args[0]))
        u, s, v, err = _hsvd_rank_jit(*args, syrk_ok=True)
        want_s = np.linalg.svd(xh.astype(np.float64), compute_uv=False)[:10]
        np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-5)
        un = np.asarray(u, np.float64)
        np.testing.assert_allclose(un.T @ un, np.eye(10), atol=1e-4)
