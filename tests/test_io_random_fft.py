"""IO roundtrips, RNG reproducibility, FFT parity sweep.

Reference coverage model: heat/core/tests/test_io.py (894 LoC, tmp
HDF5/CSV files), test_random.py (Threefry process-count independence,
test_random.py:427+), heat/fft/tests/test_fft.py.
"""

import os

import numpy as np
import pytest


class TestIO:
    def test_csv_roundtrip(self, ht, tmp_path):
        a_np = np.arange(20, dtype=np.float32).reshape(5, 4)
        p = str(tmp_path / "x.csv")
        a = ht.array(a_np, split=0)
        ht.save_csv(a, p)
        for split in (None, 0):
            b = ht.load_csv(p, split=split)
            np.testing.assert_allclose(b.numpy(), a_np)

    def test_csv_header_and_sep(self, ht, tmp_path):
        p = str(tmp_path / "h.csv")
        with open(p, "w") as f:
            f.write("a;b\n1;2\n3;4\n")
        b = ht.load_csv(p, sep=";", header_lines=1, split=0)
        np.testing.assert_allclose(b.numpy(), [[1, 2], [3, 4]])

    @pytest.mark.skipif(
        not pytest.importorskip("heat_tpu").io.supports_hdf5(), reason="h5py missing"
    )
    def test_hdf5_roundtrip(self, ht, tmp_path):
        a_np = np.random.default_rng(3).standard_normal((13, 6)).astype(np.float32)
        p = str(tmp_path / "x.h5")
        ht.save_hdf5(ht.array(a_np, split=0), p, "data")
        for split in (None, 0, 1):
            b = ht.load_hdf5(p, "data", split=split)
            np.testing.assert_allclose(b.numpy(), a_np, rtol=1e-6)

    def test_hdf5_load_fraction(self, ht, tmp_path):
        if not ht.io.supports_hdf5():
            pytest.skip("h5py missing")
        a_np = np.arange(40, dtype=np.float32).reshape(10, 4)
        p = str(tmp_path / "f.h5")
        ht.save_hdf5(ht.array(a_np), p, "d")
        b = ht.load_hdf5(p, "d", split=0, load_fraction=0.5)
        assert b.shape[0] == 5
        np.testing.assert_allclose(b.numpy(), a_np[:5])

    def test_load_save_dispatch(self, ht, tmp_path):
        a_np = np.arange(12, dtype=np.float32).reshape(3, 4)
        p = str(tmp_path / "d.csv")
        ht.save(ht.array(a_np, split=0), p)
        np.testing.assert_allclose(ht.load(p, split=0).numpy(), a_np)
        if ht.io.supports_hdf5():
            p2 = str(tmp_path / "d.h5")
            ht.save(ht.array(a_np, split=0), p2, "data")
            np.testing.assert_allclose(ht.load(p2, "data", split=0).numpy(), a_np)

    def test_npy_shards(self, ht, tmp_path):
        rng = np.random.default_rng(0)
        parts = [rng.standard_normal((3, 4)).astype(np.float32) for _ in range(3)]
        d = tmp_path / "shards"
        d.mkdir()
        for i, part in enumerate(parts):
            np.save(str(d / f"p{i}.npy"), part)
        b = ht.load_npy_from_path(str(d), dtype=ht.float32, split=0)
        np.testing.assert_allclose(b.numpy(), np.concatenate(parts, 0), rtol=1e-6)


class TestRandomReproducibility:
    def test_seed_reproducible(self, ht):
        ht.random.seed(77)
        a = ht.random.rand(6, 5, split=0).numpy()
        ht.random.seed(77)
        b = ht.random.rand(6, 5, split=0).numpy()
        np.testing.assert_array_equal(a, b)

    def test_split_independence(self, ht):
        """Threefry invariant (test_random.py:427+): same seed -> identical
        global sequence regardless of how the array is distributed."""
        draws = {}
        for split in (None, 0, 1):
            ht.random.seed(123)
            draws[split] = ht.random.rand(7, 6, split=split).numpy()
        np.testing.assert_array_equal(draws[None], draws[0])
        np.testing.assert_array_equal(draws[None], draws[1])

    def test_get_set_state(self, ht):
        ht.random.seed(5)
        _ = ht.random.rand(4, split=0)
        state = ht.random.get_state()
        a = ht.random.rand(8, split=0).numpy()
        ht.random.set_state(state)
        b = ht.random.rand(8, split=0).numpy()
        np.testing.assert_array_equal(a, b)

    def test_randint_bounds_and_dtype(self, ht):
        x = ht.random.randint(3, 9, size=(50,), split=0)
        v = x.numpy()
        assert v.min() >= 3 and v.max() < 9
        assert np.issubdtype(v.dtype, np.integer)

    def test_randperm_permutation(self, ht):
        p = ht.random.randperm(17, split=0).numpy()
        np.testing.assert_array_equal(np.sort(p), np.arange(17))
        x = ht.random.permutation(ht.arange(11, split=0)).numpy()
        np.testing.assert_array_equal(np.sort(x), np.arange(11))

    def test_normal_moments(self, ht):
        ht.random.seed(9)
        x = ht.random.normal(2.0, 3.0, (20000,), split=0).numpy()
        assert abs(x.mean() - 2.0) < 0.1
        assert abs(x.std() - 3.0) < 0.1


class TestFFTParity:
    @pytest.fixture
    def data(self):
        rng = np.random.default_rng(1)
        return rng.standard_normal((12, 10)).astype(np.float64)

    @pytest.mark.parametrize("split", [None, 0, 1])
    @pytest.mark.parametrize("axis", [0, 1, -1])
    def test_fft_ifft(self, ht, data, split, axis):
        x = ht.array(data, split=split)
        np.testing.assert_allclose(
            ht.fft.fft(x, axis=axis).numpy(), np.fft.fft(data, axis=axis), rtol=1e-9, atol=1e-9
        )
        np.testing.assert_allclose(
            ht.fft.ifft(ht.fft.fft(x, axis=axis), axis=axis).numpy(),
            data,
            rtol=1e-9,
            atol=1e-9,
        )

    @pytest.mark.parametrize("split", [None, 0, 1])
    def test_rfft_irfft(self, ht, data, split):
        x = ht.array(data, split=split)
        np.testing.assert_allclose(
            ht.fft.rfft(x).numpy(), np.fft.rfft(data), rtol=1e-9, atol=1e-9
        )
        np.testing.assert_allclose(
            ht.fft.irfft(ht.fft.rfft(x), n=data.shape[-1]).numpy(), data, rtol=1e-9, atol=1e-9
        )

    @pytest.mark.parametrize("split", [None, 0])
    def test_fft2_fftn(self, ht, data, split):
        x = ht.array(data, split=split)
        np.testing.assert_allclose(ht.fft.fft2(x).numpy(), np.fft.fft2(data), rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(ht.fft.fftn(x).numpy(), np.fft.fftn(data), rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(
            ht.fft.rfftn(x).numpy(), np.fft.rfftn(data), rtol=1e-9, atol=1e-9
        )

    def test_hfft_ihfft(self, ht, data):
        row = data[0]
        x = ht.array(row, split=0)
        np.testing.assert_allclose(
            ht.fft.hfft(x).numpy(), np.fft.hfft(row), rtol=1e-9, atol=1e-8
        )
        np.testing.assert_allclose(
            ht.fft.ihfft(x).numpy(), np.fft.ihfft(row), rtol=1e-9, atol=1e-9
        )

    def test_fftfreq_shift(self, ht, data):
        np.testing.assert_allclose(ht.fft.fftfreq(10, 0.1).numpy(), np.fft.fftfreq(10, 0.1), rtol=1e-6)
        np.testing.assert_allclose(
            ht.fft.rfftfreq(10, 0.1).numpy(), np.fft.rfftfreq(10, 0.1), rtol=1e-6
        )
        x = ht.array(data, split=0)
        np.testing.assert_allclose(
            ht.fft.fftshift(x).numpy(), np.fft.fftshift(data), rtol=1e-9
        )
        np.testing.assert_allclose(
            ht.fft.ifftshift(ht.fft.fftshift(x)).numpy(), data, rtol=1e-9
        )


class TestBundledDatasets:
    """The datasets package (analog of heat/datasets: iris/diabetes files)."""

    def test_iris_h5(self, ht):
        X = ht.load_hdf5(ht.datasets.path("iris.h5"), dataset="data", split=0)
        assert X.shape == (150, 4)
        assert float(X.min()) > 0.0

    def test_diabetes_h5(self, ht):
        X = ht.load_hdf5(ht.datasets.path("diabetes.h5"), dataset="x", split=0)
        y = ht.load_hdf5(ht.datasets.path("diabetes.h5"), dataset="y", split=0)
        assert X.shape == (442, 10)
        assert y.shape == (442, 1)

    def test_iris_csv(self, ht):
        X = ht.load_csv(ht.datasets.path("iris.csv"), sep=";", split=0)
        assert X.shape == (150, 4)

    def test_missing_dataset(self, ht):
        import pytest as _pytest

        with _pytest.raises(FileNotFoundError, match="iris.h5"):
            ht.datasets.path("nope.h5")


class TestHermitianND:
    """hfftn/ihfftn/hfft2/ihfft2 — jnp has no native versions; the chained
    composition was verified against torch.fft for all norms."""

    def test_hfftn_ihfftn_vs_torch(self, ht):
        import torch

        rng = np.random.default_rng(0)
        a = (rng.standard_normal((4, 6, 5)) + 1j * rng.standard_normal((4, 6, 5))).astype(
            np.complex64
        )
        x = ht.array(a, split=0)
        for norm in (None, "ortho", "forward"):
            want = torch.fft.hfftn(torch.tensor(a), norm=norm or "backward").numpy()
            got = ht.fft.hfftn(x, norm=norm).numpy()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        b = rng.standard_normal((4, 6, 5)).astype(np.float32)
        for norm in (None, "ortho", "forward"):
            want = torch.fft.ihfftn(torch.tensor(b), norm=norm or "backward").numpy()
            got = ht.fft.ihfftn(ht.array(b, split=0), norm=norm).numpy()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_hfft2_ihfft2_vs_torch(self, ht):
        import torch

        rng = np.random.default_rng(1)
        a = (rng.standard_normal((3, 6, 5)) + 1j * rng.standard_normal((3, 6, 5))).astype(
            np.complex64
        )
        want = torch.fft.hfft2(torch.tensor(a)).numpy()
        got = ht.fft.hfft2(ht.array(a, split=0)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        b = rng.standard_normal((3, 6, 5)).astype(np.float32)
        want = torch.fft.ihfft2(torch.tensor(b)).numpy()
        got = ht.fft.ihfft2(ht.array(b, split=0)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_hermitian_chain_axes_subset_vs_torch(self, ht):
        import torch

        rng = np.random.default_rng(2)
        a = (rng.standard_normal((4, 5, 6)) + 1j * rng.standard_normal((4, 5, 6))).astype(
            np.complex64
        )
        for norm in (None, "ortho", "forward"):
            want = torch.fft.hfftn(
                torch.tensor(a), dim=(0, 2), norm=norm or "backward"
            ).numpy()
            got = ht.fft.hfftn(ht.array(a, split=1), axes=(0, 2), norm=norm).numpy()
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        b_ = rng.standard_normal((4, 5, 6)).astype(np.float32)
        want = torch.fft.ihfftn(torch.tensor(b_), dim=(0, 2)).numpy()
        got = ht.fft.ihfftn(ht.array(b_, split=1), axes=(0, 2)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


class TestShardedWrites:
    """Streaming per-shard writers (reference io.py:597-680 mpio/serialized
    rank writes, io.py:1145 per-rank npy shards)."""

    def test_npy_shard_roundtrip_uneven(self, ht, tmp_path):
        x = np.arange(13 * 4, dtype=np.float64).reshape(13, 4)
        a = ht.array(x, split=0)
        d = str(tmp_path / "arr")
        ht.save_npy_from_path(a, d)
        import os

        files = sorted(os.listdir(d))
        assert len(files) > 1  # one slab per (non-empty) shard
        assert files == sorted(files)  # offset order == lexicographic
        b = ht.load_npy_from_path(d, dtype=ht.float64, split=0)
        np.testing.assert_array_equal(b.numpy(), x)

    def test_npy_shard_replicated(self, ht, tmp_path):
        x = np.arange(6, dtype=np.float32)
        d = str(tmp_path / "rep")
        ht.save_npy_from_path(ht.array(x), d)
        b = ht.load_npy_from_path(d, dtype=ht.float32, split=None)
        np.testing.assert_array_equal(b.numpy(), x)

    @pytest.mark.parametrize("split", [0, 1])
    def test_hdf5_streams_without_gather(self, ht, tmp_path, monkeypatch, split):
        """save_hdf5 must never materialize the global array — .numpy() and
        ._dense() stay untouched during the write."""
        if not ht.io.supports_hdf5():
            pytest.skip("h5py missing")
        rng = np.random.default_rng(5)
        x = rng.standard_normal((13, 6))
        a = ht.array(x, split=split)

        from heat_tpu.core.dndarray import DNDarray

        def boom(self, *args, **kwargs):
            raise AssertionError("save_hdf5 gathered the global array")

        monkeypatch.setattr(DNDarray, "numpy", boom)
        monkeypatch.setattr(DNDarray, "_dense", boom)
        p = str(tmp_path / "s.h5")
        ht.save_hdf5(a, p, "data")
        monkeypatch.undo()

        b = ht.load_hdf5(p, "data", dtype=ht.float64, split=split)
        np.testing.assert_array_equal(b.numpy(), x)


class TestPencilFFT:
    """Split-axis FFT as an all_to_all pencil transpose (reference
    fft.py:100-137), never an all-gather."""

    @pytest.mark.parametrize("shape,axis", [((64, 32), 0), ((61, 32), 0), ((40, 24, 8), 0), ((16, 64), 1)])
    def test_pencil_matches_numpy(self, ht, shape, axis):
        rng = np.random.default_rng(1)
        x = rng.standard_normal(shape)
        a = ht.array(x, split=axis)
        np.testing.assert_allclose(
            ht.fft.fft(a, axis=axis).numpy(), np.fft.fft(x, axis=axis), atol=1e-10
        )
        np.testing.assert_allclose(
            ht.fft.ifft(ht.fft.fft(a, axis=axis), axis=axis).numpy().real, x, atol=1e-10
        )
        for norm in ("ortho", "forward"):
            np.testing.assert_allclose(
                ht.fft.fft(a, axis=axis, norm=norm).numpy(),
                np.fft.fft(x, axis=axis, norm=norm),
                atol=1e-10,
            )

    def test_pencil_fftn_norm_composition(self, ht):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((24, 16, 8))
        a = ht.array(x, split=0)
        for norm in (None, "ortho", "forward"):
            np.testing.assert_allclose(
                ht.fft.fftn(a, norm=norm).numpy(), np.fft.fftn(x, norm=norm), atol=1e-9
            )
        np.testing.assert_allclose(ht.fft.ifftn(ht.fft.fftn(a)).numpy().real, x, atol=1e-10)

    def test_pencil_compiles_to_all_to_all_only(self, ht):
        import importlib

        fft_mod = importlib.import_module("heat_tpu.fft.fft")
        p = ht.get_comm().size
        a = ht.array(np.zeros((3 * p, 2 * p, 8)), split=0)
        stages = fft_mod._planned(fft_mod._stages("fft", ((0, None),), 0, None), a.comm, 0, (3 * p, 2 * p, 8), 3 * p, np.dtype(np.complex128))
        fn = fft_mod._slab_program(a.comm, 0, 3, 3 * p, stages)
        txt = fn.lower(a.larray_padded.astype(np.complex128)).compile().as_text()
        assert "all-to-all" in txt
        assert "all-gather" not in txt

    def test_pencil_pads_a_partner_the_mesh_does_not_divide(self, ht):
        # no partner axis divisible by the mesh -> the partner is padded on the device, still correct
        rng = np.random.default_rng(3)
        x = rng.standard_normal((40, 7))
        a = ht.array(x, split=0)
        np.testing.assert_allclose(ht.fft.fft(a, axis=0).numpy(), np.fft.fft(x, axis=0), atol=1e-10)


class TestPlanarFFT:
    """Real-pair (planar) execution: complex transforms as two real planes
    so they run on accelerators that reject complex dtypes (VERDICT r2 #1;
    reference capability heat/fft/fft.py:40-298)."""

    @pytest.fixture(autouse=True)
    def _force_planar(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_PLANAR", "1")

    def test_fftn_roundtrip_planar_backed(self, ht):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 12, 10)).astype(np.float32)
        a = ht.array(x, split=0)
        f = ht.fft.fftn(a)
        assert f._planar is not None  # stays on the mesh as planes
        np.testing.assert_allclose(f.numpy(), np.fft.fftn(x), rtol=2e-4, atol=1e-3)
        # chained planar op consumes the planes without materializing
        back = ht.fft.ifftn(f)
        assert back._planar is not None
        np.testing.assert_allclose(back.numpy().real, x, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("split", [None, 0, 1])
    def test_kinds_match_numpy(self, ht, split):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((12, 10)).astype(np.float32)
        a = ht.array(x, split=split)
        np.testing.assert_allclose(
            ht.fft.rfft(a).numpy(), np.fft.rfft(x), rtol=2e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            ht.fft.ihfft(a, norm="ortho").numpy(),
            np.fft.ihfft(x, norm="ortho"),
            rtol=2e-4,
            atol=1e-4,
        )
        z = (rng.standard_normal((12, 10)) + 1j * rng.standard_normal((12, 10))).astype(
            np.complex64
        )
        c = ht.array(z, split=split)
        np.testing.assert_allclose(
            ht.fft.irfft(c, n=9).numpy(), np.fft.irfft(z, n=9), rtol=2e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            ht.fft.hfft(c).numpy(), np.fft.hfft(z), rtol=2e-4, atol=1e-3
        )

    def test_split_axis_uses_planar_pencil(self, ht):
        p = ht.get_comm().size
        if p == 1:
            pytest.skip("needs a mesh")
        rng = np.random.default_rng(2)
        x = rng.standard_normal((5 * p, 2 * p)).astype(np.float32)
        a = ht.array(x, split=0)
        f = ht.fft.fft(a, axis=0)
        assert f._planar is not None and f.split == 0
        np.testing.assert_allclose(f.numpy(), np.fft.fft(x, axis=0), rtol=2e-4, atol=1e-3)
        import importlib

        fft_mod = importlib.import_module("heat_tpu.fft.fft")
        fn = fft_mod._pencil_planar_kind_fn(a.comm, "fft", 0, 1, 5 * p, None, 2, None, True)
        re, im = fft_mod._padded_planes(a)
        txt = fn.lower(re, im).compile().as_text()
        assert "all-to-all" in txt and "all-gather" not in txt

    def test_complex_math_plane_fast_paths(self, ht):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((8, 6)).astype(np.float32)
        f = ht.fft.fft(ht.array(x, split=0))
        assert f._planar is not None
        want = np.fft.fft(x)
        np.testing.assert_allclose(f.real.numpy(), want.real, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(f.imag.numpy(), want.imag, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(
            ht.conj(f).numpy(), np.conj(want), rtol=2e-4, atol=1e-4
        )
        # compare angles modulo 2*pi: a ~1e-17 imaginary rounding flips the
        # branch cut between -pi and +pi for real-negative bins
        dang = ht.angle(f).numpy() - np.angle(want)
        np.testing.assert_allclose(
            (dang + np.pi) % (2 * np.pi) - np.pi, np.zeros_like(dang), atol=1e-3
        )
        np.testing.assert_allclose(ht.abs(f).numpy(), np.abs(want), rtol=2e-4, atol=1e-4)
        assert ht.conj(f)._planar is not None  # conj stays planar
        sh = ht.fft.fftshift(f)
        assert sh._planar is not None
        np.testing.assert_allclose(sh.numpy(), np.fft.fftshift(want), rtol=2e-4, atol=1e-4)

    def test_materialization_and_mutation_invalidates(self, ht):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((6, 4)).astype(np.float32)
        f = ht.fft.fft(ht.array(x, split=0))
        want = np.fft.fft(x).astype(np.complex64)
        # generic (non-planar-aware) op: materializes transparently
        s = (f + f).numpy()
        np.testing.assert_allclose(s, 2 * want, rtol=2e-4, atol=1e-4)
        # in-place mutation must drop the stale planes
        f[0, 0] = 0.0
        assert f._planar is None
        got = f.numpy()
        want[0, 0] = 0.0
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-4)

    def test_rfft_rejects_complex_like_numpy(self, ht):
        rng = np.random.default_rng(6)
        z = (rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))).astype(
            np.complex64
        )
        c = ht.array(z, split=0)
        for fn in (ht.fft.rfft, ht.fft.ihfft, ht.fft.rfftn, ht.fft.ihfftn):
            with pytest.raises(TypeError):
                fn(c)

    def test_odd_sizes_and_prime_lengths(self, ht):
        rng = np.random.default_rng(5)
        for n in (13, 521):  # prime (Bluestein past the matmul cutoff for 521)
            x = rng.standard_normal(n).astype(np.float32)
            f = ht.fft.fft(ht.array(x, split=0))
            np.testing.assert_allclose(
                f.numpy(), np.fft.fft(x), rtol=2e-3, atol=2e-3
            )
