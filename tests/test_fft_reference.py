"""``ht.fft`` on split arrays against the plain reference of the chip
benchmark's FFT cell (``chipbench/drivers/fftn_pencil.py``: the transform by
its definition, as real matrix products), the reference itself against NumPy,
and what the compiled programs of the split path hold (PR 31).

A split array is transformed by ONE ``shard_map`` program: the pencil along
the split axis (two ``all_to_all``), XLA's ``fft`` on each device's own slab
along the others.  ``jnp.fft`` on the sharded global array, which is what
ran before, compiles on this mesh to an all-gather of the whole array.
"""

import importlib
import os
import re
import sys

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402

fft_mod = importlib.import_module("heat_tpu.fft.fft")
driver = load_py("drivers", "fftn_pencil")


def _cube(side, complex_input, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((side,) * 3)
    return x + 1j * rng.standard_normal((side,) * 3) if complex_input else x


def _reference(x, inverse):
    re, im = driver.dft3(np.real(x), np.imag(x) if np.iscomplexobj(x) else None, inverse=inverse)
    return np.asarray(re) + 1j * np.asarray(im)


@pytest.fixture()
def programs(monkeypatch):
    """Every program the split path asks for, as (key, program) pairs."""
    asked = []
    original = fft_mod._slab_program

    def recording(*key):
        asked.append((key, original(*key)))
        return asked[-1][1]

    monkeypatch.setattr(fft_mod, "_slab_program", recording)
    return asked


SCOPES = ("fft.alltoall.in", "fft.split_axis", "fft.alltoall.out", "fft.local")


def _collectives(program, padded, scopes=()):
    """(all-to-alls, all-gathers) of the compiled program; ``scopes`` have to
    stand in its operations' ``op_name``."""
    txt = program.lower(padded).compile().as_text()
    assert all(f"/{scope}/" in txt for scope in scopes), [s for s in scopes if f"/{s}/" not in txt]
    return len(re.findall(r" all-to-all\(", txt)), txt.count("all-gather")


@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
@pytest.mark.parametrize("complex_input", [False, True], ids=["real", "complex"])
@pytest.mark.parametrize("side", [8, 13])
def test_reference_is_the_transform_numpy_computes(side, complex_input, inverse):
    """The reference works in float32 (matrices rounded from float64 ones,
    products at ``highest``), so it stands some 1e-7 from NumPy's float64
    transform, relative to the spectrum's largest coefficient."""
    x = _cube(side, complex_input)
    want = (np.fft.ifftn if inverse else np.fft.fftn)(x)
    assert np.max(np.abs(_reference(x, inverse) - want)) <= 2e-6 * np.max(np.abs(want))


@pytest.mark.parametrize("split", [0, 1, 2, None])
@pytest.mark.parametrize("entry", ["fftn", "ifftn"])
@pytest.mark.parametrize("complex_input", [False, True], ids=["real", "complex"])
@pytest.mark.parametrize("side", [16, 13], ids=["mesh_divides", "mesh_does_not_divide"])
def test_fftn_against_the_plain_reference(programs, side, complex_input, entry, split):
    x = _cube(side, complex_input)
    a = ht.array(x, split=split)
    y = getattr(ht.fft, entry)(a)
    want = _reference(x, inverse=entry == "ifftn")
    assert y.split == split and y.shape == x.shape
    assert np.max(np.abs(y.numpy() - want)) <= 2e-6 * np.max(np.abs(want))
    if split is None:
        assert programs == []  # nothing is split: jnp.fft on the whole array
        return
    ((key, program),) = programs  # one program a call
    assert _collectives(program, a.larray_padded, SCOPES) == (2, 0)
    assert y.larray_padded.sharding.is_equivalent_to(a.comm.sharding(split), 3)


#: entry -> (arguments, the NumPy function with the same arguments, or None where NumPy has none)
SIBLINGS = {
    "fft": ({"axis": 1}, np.fft.fft), "ifft": ({"axis": 2}, np.fft.ifft),
    "fft2": ({}, np.fft.fft2), "ifft2": ({}, np.fft.ifft2),
    "rfft": ({}, np.fft.rfft), "irfft": ({"n": 10}, np.fft.irfft),
    "rfft2": ({}, np.fft.rfft2), "irfft2": ({"s": (12, 10)}, np.fft.irfft2),
    "rfftn": ({"axes": (1, 2)}, np.fft.rfftn), "irfftn": ({"s": (12, 10), "axes": (1, 2)}, np.fft.irfftn),
    "hfft": ({"n": 10}, np.fft.hfft), "ihfft": ({}, np.fft.ihfft),
    "hfft2": ({"s": (12, 10)}, None), "ihfft2": ({}, None),
    "hfftn": ({"s": (12, 10), "axes": (1, 2)}, None), "ihfftn": ({"axes": (1, 2)}, None),
}


@pytest.mark.parametrize("entry", sorted(SIBLINGS))
def test_siblings_transform_each_devices_own_slab(programs, entry):
    """Axes that are not split: the transform runs inside the ``shard_map``
    on each device's slab, pad rows included, and nothing is exchanged."""
    kwargs, numpy_fn = SIBLINGS[entry]
    rng = np.random.default_rng(9)
    x = rng.standard_normal((13, 12, 10))
    if entry.startswith(("i", "h")) and entry not in ("ihfft", "ihfft2", "ihfftn"):
        x = x + 1j * rng.standard_normal(x.shape)
    a = ht.array(x, split=0)
    y = getattr(ht.fft, entry)(a, **kwargs)
    want = numpy_fn(x, **kwargs) if numpy_fn else getattr(ht.fft, entry)(ht.array(x), **kwargs).numpy()
    assert y.split == 0 and y.shape == want.shape
    np.testing.assert_allclose(y.numpy(), want, atol=1e-9 * np.max(np.abs(want)))
    ((key, program),) = programs
    assert _collectives(program, a.larray_padded, SCOPES[3:]) == (0, 0)


#: a real transform along the split axis, and chains that cross it: the pencil takes every kind
CROSSING = {
    "rfft_split_last": ("rfft", {"axis": 0}, 0, False), "irfft_split_last": ("irfft", {"axis": 0, "n": 13}, 0, True),
    "rfftn_split_first": ("rfftn", {}, 0, False), "rfftn_split_last": ("rfftn", {}, 2, False),
    "irfftn_split_mid": ("irfftn", {"s": (13, 12, 10)}, 1, True), "hfft_split": ("hfft", {"axis": 1, "n": 12}, 1, True),
    "ihfftn_split_last": ("ihfftn", {}, 2, False), "fftn_sized": ("fftn", {"s": (9, 16, 10)}, 0, True),
    "ifft_split_sized": ("ifft", {"axis": 0, "n": 20}, 0, True),
}


@pytest.mark.parametrize("case", sorted(CROSSING))
def test_every_kind_crosses_the_split_axis_by_the_pencil(programs, case):
    entry, kwargs, split, complex_input = CROSSING[case]
    rng = np.random.default_rng(13)
    x = rng.standard_normal((13, 12, 10))
    if complex_input:
        x = x + 1j * rng.standard_normal(x.shape)
    a = ht.array(x, split=split)
    y = getattr(ht.fft, entry)(a, **kwargs)
    want = getattr(ht.fft, entry)(ht.array(x), **kwargs).numpy()  # the dense route, held to NumPy elsewhere
    assert y.split == split and y.shape == want.shape
    np.testing.assert_allclose(y.numpy(), want, atol=1e-9 * np.max(np.abs(want)))
    ((key, program),) = programs
    assert _collectives(program, a.larray_padded) == (2, 0)


@pytest.mark.parametrize("planar", [False, True], ids=["default_engine", "planar_engine"])
def test_a_traced_pencil_counts_its_two_all_to_alls(monkeypatch, planar):
    """``comm.all_to_all`` accounts a collective when the program is traced:
    two a pencil in the default engine (a complex array is one operand),
    two a plane in the planar engine's (``fft`` ships a zero imaginary plane
    beside a real input: four)."""
    if planar:
        monkeypatch.setenv("HEAT_TPU_PLANAR", "1")
    calls = telemetry.metrics.counter("comm.calls.all_to_all")
    sent = telemetry.metrics.counter("comm.bytes.all_to_all")
    a = ht.array(np.random.default_rng(1).standard_normal((24, 8, 7 if planar else 9)), split=0)  # a shape of its own
    before, bytes_before = calls.value, sent.value
    ht.fft.fft(a, axis=0)
    assert calls.value - before == (4 if planar else 2)
    assert sent.value > bytes_before
    ht.fft.fft(a, axis=0)  # the cached program is not traced again
    assert calls.value - before == (4 if planar else 2)
