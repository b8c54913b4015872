"""Planar pencil breadth (VERDICT r3 missing #3 / next #4): every
transform kind along a split axis rides the all_to_all pencil — explicit
``n``, Hermitian length changes, and non-divisible partner axes included —
and none of their programs contains an all-gather.

Reference parity: heat/fft/fft.py:66-137 (the pencil covers every kind).
"""

import os
import re as _re

import jax
import numpy as np
import pytest

import importlib

import heat_tpu as ht

fft_mod = importlib.import_module("heat_tpu.fft.fft")


@pytest.fixture(autouse=True)
def planar_mode():
    os.environ["HEAT_TPU_PLANAR"] = "1"
    try:
        yield
    finally:
        del os.environ["HEAT_TPU_PLANAR"]


P = jax.device_count()  # conftest mesh (8 default; CI sweeps 3)
TOL = dict(rtol=2e-4, atol=1e-3)


def _np_op(kind):
    return getattr(np.fft, kind)


@pytest.mark.parametrize("kind", ["fft", "ifft", "rfft", "ihfft"])
@pytest.mark.parametrize("n", [None, 24, 40])  # shrink and grow vs 32
def test_pencil_forward_kinds_split0(kind, n):
    rng = np.random.default_rng(5)
    x = rng.standard_normal((32, 2 * P)).astype(np.float32)
    if kind in ("fft", "ifft"):
        a = ht.array(x, split=0)
        got = getattr(ht.fft, kind)(a, n=n, axis=0)
        assert got._planar is not None and got.split == 0
        np.testing.assert_allclose(got.numpy(), _np_op(kind)(x, n=n, axis=0), **TOL)
    else:
        a = ht.array(x, split=0)
        got = getattr(ht.fft, kind)(a, n=n, axis=0)
        assert got._planar is not None and got.split == 0
        np.testing.assert_allclose(got.numpy(), _np_op(kind)(x, n=n, axis=0), **TOL)


@pytest.mark.parametrize("kind", ["irfft", "hfft"])
@pytest.mark.parametrize("n", [None, 30, 50])
def test_pencil_real_output_kinds_split0(kind, n):
    rng = np.random.default_rng(7)
    z = (rng.standard_normal((17, 2 * P)) + 1j * rng.standard_normal((17, 2 * P))).astype(
        np.complex64
    )
    a = ht.fft.fft(ht.array(z.real.astype(np.float32), split=0), axis=1)  # planar source
    # overwrite with a controlled Hermitian-half input: build from z via planes
    a = ht.array(z, split=0)
    got = getattr(ht.fft, kind)(a, n=n, axis=0)
    want = _np_op(kind)(z, n=n, axis=0)
    assert got.split == 0
    assert got._planar is None  # real output
    np.testing.assert_allclose(got.numpy(), want, **TOL)


def test_pencil_nondivisible_partner():
    """No axis divisible by the mesh: the partner is padded locally, not
    resharded through GSPMD (the r3 fallback this replaces)."""
    rng = np.random.default_rng(9)
    x = rng.standard_normal((3 * P, 13)).astype(np.float32)  # 13 % 8 != 0
    a = ht.array(x, split=0)
    got = ht.fft.fft(a, axis=0)
    assert got._planar is not None and got.split == 0
    np.testing.assert_allclose(got.numpy(), np.fft.fft(x, axis=0), **TOL)
    # rfft with the ragged partner and explicit n
    got2 = ht.fft.rfft(a, n=20, axis=0)
    np.testing.assert_allclose(got2.numpy(), np.fft.rfft(x, n=20, axis=0), **TOL)


def test_pencil_split1_and_rfftn():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2 * P, 48)).astype(np.float32)
    a = ht.array(x, split=1)
    got = ht.fft.rfft(a, axis=1)
    assert got.split == 1
    np.testing.assert_allclose(got.numpy(), np.fft.rfft(x, axis=1), **TOL)
    # rfftn with the real axis ON the split: real pencil + local complex pass
    got2 = ht.fft.rfftn(ht.array(x, split=1))
    np.testing.assert_allclose(got2.numpy(), np.fft.rfftn(x), **TOL)
    # irfftn back
    back = ht.fft.irfftn(got2, s=x.shape)
    np.testing.assert_allclose(back.numpy(), x, rtol=1e-3, atol=2e-3)


@pytest.mark.parametrize(
    "kind,have_im", [("fft", True), ("ifft", True), ("rfft", False),
                     ("ihfft", False), ("irfft", True), ("hfft", True)]
)
def test_pencil_hlo_no_allgather(kind, have_im):
    """The compiled pencil program for EVERY kind moves data only through
    all-to-alls (VERDICT r3 #4's done-bar)."""
    import jax

    comm = ht.get_comm()
    n_true = 32
    fn = fft_mod._pencil_planar_kind_fn(comm, kind, 0, 1, n_true, None, 2, None, have_im)
    shp = jax.ShapeDtypeStruct((comm.padded_extent(n_true), 2 * P), np.float32)
    args = (shp, shp) if have_im else (shp,)
    txt = fn.lower(*args).compile().as_text()
    assert "all-gather" not in txt, f"{kind} pencil gathered"
    assert "all-to-all" in txt


def test_fftn_split_axis_no_gather_end_to_end():
    rng = np.random.default_rng(13)
    x = rng.standard_normal((2 * P, 12, 10)).astype(np.float32)
    a = ht.array(x, split=0)
    got = ht.fft.fftn(a)
    assert got._planar is not None and got.split == 0
    np.testing.assert_allclose(got.numpy(), np.fft.fftn(x), rtol=1e-3, atol=5e-3)
    back = ht.fft.ifftn(got)
    np.testing.assert_allclose(back.numpy(), x, rtol=1e-3, atol=2e-3)


# ----------------------------------------------------------------------
# The default engine's pencil, block by block (PR 32): a slab is cut along a
# bystander axis into blocks that are exchanged in, transformed and exchanged
# back on their own.  The blocked program is held to the one-block program
# (PR 31's, operation for operation) and to ``numpy.fft``.
# ----------------------------------------------------------------------
from heat_tpu import telemetry  # noqa: E402

DIVIDES = (2 * P, 3 * P, 8)      # split 0 and 1: the mesh divides the split axis and a partner; cut 8 or more rows
RAGGED = (13, 12, 10)            # the mesh (8, or CI's 3) divides no extent but perhaps 12

#: entry -> (keyword arguments given the shape and the split, input: "real", "complex" or "both")
BLOCKED_KINDS = {
    "fftn": (lambda shape, split: {}, "both"),
    "ifftn": (lambda shape, split: {}, "both"),
    "rfftn": (lambda shape, split: {}, "real"),
    "irfftn": (lambda shape, split: {"s": shape, "axes": (0, 1, 2)}, "complex"),
    "hfftn": (lambda shape, split: {"s": shape, "axes": (0, 1, 2)}, "complex"),
    "ihfftn": (lambda shape, split: {}, "real"),
    "fft": (lambda shape, split: {"axis": split}, "both"),
    "ifft": (lambda shape, split: {"axis": split, "n": shape[split] + 3}, "both"),
    "rfft": (lambda shape, split: {"axis": split}, "real"),
    "irfft": (lambda shape, split: {"axis": split, "n": shape[split]}, "complex"),
    "hfft": (lambda shape, split: {"axis": split, "n": shape[split]}, "complex"),
    "ihfft": (lambda shape, split: {"axis": split}, "real"),
    "fft2": (lambda shape, split: {"axes": (split, (split + 1) % 3)}, "both"),
    "irfft2": (lambda shape, split: {"axes": ((split + 1) % 3, split), "s": (shape[(split + 1) % 3], shape[split])}, "complex"),
}
BLOCKED_CASES = [
    (entry, complex_input, split, extents)
    for entry, (_, takes) in sorted(BLOCKED_KINDS.items())
    for complex_input in (False, True) if takes in ("both", "complex" if complex_input else "real")
    for split in (0, 1, 2)
    for extents in ("divides", "ragged")
]


#: A compile option the CPU's compiler accepts, at its default: what stands in
#: for the TPU's ``xla_tpu_enable_async_all_to_all`` where a test has the
#: forced-host mesh run the blocked program (a mesh without such an option
#: takes one block, whatever the slab's size).
CPU_OPTION = {"xla_llvm_disable_expensive_passes": False}


def _block_bytes(monkeypatch, block_bytes, most=4, options=CPU_OPTION):
    """Blocks of ``block_bytes`` a plane or more, ``most`` at most, on a mesh
    whose compile ``options`` put an exchange beside compute, for programs
    traced from now on."""
    monkeypatch.setattr(fft_mod, "_PENCIL_BLOCK_BYTES", block_bytes)
    monkeypatch.setattr(fft_mod, "_PENCIL_BLOCKS_MAX", most)
    monkeypatch.setattr(type(ht.get_comm()), "overlap_compiler_options", lambda self: dict(options))
    fft_mod._slab_program.cache_clear()
    fft_mod._planned.cache_clear()


@pytest.fixture()
def default_engine(monkeypatch):
    """The module's tests run the planar engine; these the default one."""
    monkeypatch.delenv("HEAT_TPU_PLANAR", raising=False)
    yield
    fft_mod._slab_program.cache_clear()
    fft_mod._planned.cache_clear()


def _traced(entry, x, split, kwargs):
    """``ht.fft.<entry>`` with tracing on: (result, its spans by name,
    ``comm.all_to_all`` calls and bytes the program's trace counted)."""
    calls, sent = (telemetry.metrics.counter(f"comm.{what}.all_to_all") for what in ("calls", "bytes"))
    before = calls.value, sent.value
    prev = telemetry.set_tracing(True)
    try:
        telemetry.clear_spans()
        y = getattr(ht.fft, entry)(ht.array(x, split=split), **kwargs)
        spans = {r.name: r for r in telemetry.get_spans()}
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
    return y, spans, calls.value - before[0], sent.value - before[1]


def _counted(monkeypatch, block_bytes, entry, x, split, kwargs):
    """``_traced`` with blocks of ``block_bytes`` or more: (result,
    ``blocks`` of the ``fft.dispatch`` span, all_to_all calls and bytes)."""
    _block_bytes(monkeypatch, block_bytes)
    y, spans, calls, sent = _traced(entry, x, split, kwargs)
    return y, spans["fft.dispatch"].attrs["blocks"], calls, sent


@pytest.mark.parametrize("entry,complex_input,split,extents", BLOCKED_CASES,
                         ids=[f"{e}-{'complex' if c else 'real'}-split{s}-{x}" for e, c, s, x in BLOCKED_CASES])
def test_blocked_pencil_is_the_one_block_pencil(default_engine, monkeypatch, entry, complex_input, split, extents):
    """Every kind, block by block, against the same program in one block and
    against NumPy; the ``blocks`` attribute is what the trace exchanged: two
    ``comm.all_to_all`` a block, and the same bytes however the slab is cut."""
    shape = DIVIDES if extents == "divides" else RAGGED
    rng = np.random.default_rng(17)
    x = rng.standard_normal(shape)
    if complex_input:
        x = x + 1j * rng.standard_normal(shape)
    kwargs = BLOCKED_KINDS[entry][0](shape, split)
    whole, one, calls_one, bytes_one = _counted(monkeypatch, 1 << 40, entry, x, split, kwargs)
    cut, blocks, calls_cut, bytes_cut = _counted(monkeypatch, 1, entry, x, split, kwargs)
    assert one == 1 and calls_one == 2
    assert calls_cut == 2 * blocks and bytes_cut == bytes_one
    assert cut.split == whole.split == split and cut.shape == whole.shape
    np.testing.assert_allclose(cut.numpy(), whole.numpy(), atol=1e-12 * np.max(np.abs(whole.numpy())))
    numpy_fn = getattr(np.fft, entry, None)
    if numpy_fn is not None:  # NumPy has no hfftn / ihfftn: the one-block program is their reference
        np.testing.assert_allclose(cut.numpy(), numpy_fn(x, **kwargs), atol=1e-9 * np.max(np.abs(whole.numpy())))
    if extents == "divides" and split == 0 and entry in ("fftn", "ifftn", "fft", "ifft", "fft2"):
        assert blocks == 4  # the bystander axis keeps its rows, a multiple of four: the most blocks this test allows


CUBE = (2 * P, 16, 2 * P)   # split 0: the last axis is the partner, the blocks are cut along the 16 rows of axis 1
CUBE_PLANE = 2 * 16 * 2 * P * 8  # bytes of a device's slab of it in float64


@pytest.mark.parametrize("shape,split,block_bytes,blocks", [
    ((4 * P, 6 * P), 0, 1, 1),                 # 2-D: no bystander axis, one block at any size
    ((4 * P, 6 * P), 1, 1, 1),
    (CUBE, 0, None, 1),                        # a small cube at the size the program ships with: one block
    (CUBE, 0, CUBE_PLANE // 2, 2),             # a plane of the slab holds two blocks of this size
    (CUBE, 0, CUBE_PLANE // 4, 4),             # and four of this
    (CUBE, 0, 1, 16),                          # never more than the program's most, 16
    ((2 * P, 32, 2 * P), 0, 1, 16),
    ((2 * P, 6, 2 * P), 0, 1, 2),              # 6 rows to cut: two blocks, not four
    ((2 * P, 7, 2 * P), 0, 1, 1),              # 7 rows: none
], ids=["2d_split0", "2d_split1", "small_cube_default", "two_blocks_fit", "four_blocks_fit", "at_most_16", "32_rows_at_most_16",
        "cut_axis_of_6", "cut_axis_of_7"])
def test_block_count_follows_the_slabs_shape_and_bytes(default_engine, monkeypatch, shape, split, block_bytes, blocks):
    """``blocks`` on the root span and on ``fft.dispatch``, the exchanges the
    trace counted, and the result, for slabs that must take one block and
    slabs cut into several (float64 planes: 8 bytes an element)."""
    x = np.random.default_rng(23).standard_normal(shape)
    assert (fft_mod._PENCIL_BLOCK_BYTES, fft_mod._PENCIL_BLOCKS_MAX) == (16 << 20, 16)  # what step 0 of PR 32 found
    _block_bytes(monkeypatch, fft_mod._PENCIL_BLOCK_BYTES if block_bytes is None else block_bytes, most=16)
    y, spans, calls, _ = _traced("fftn", x, split, {})
    assert spans["ht.fft.fftn"].attrs["blocks"] == spans["fft.dispatch"].attrs["blocks"] == blocks
    assert spans["ht.fft.fftn"].attrs["route"] == "pencil"
    assert calls == 2 * blocks
    np.testing.assert_allclose(y.numpy(), np.fft.fftn(x), atol=1e-9 * np.max(np.abs(np.fft.fftn(x))))


def test_blocked_program_exchanges_block_by_block_and_gathers_nothing(default_engine, monkeypatch):
    """The compiled program of a cube cut into four: eight all-to-alls (each
    block in, each block back), no all-gather, the pencil's scopes, and the
    barriers that keep the blocks' transforms apart and in order.  The plan
    is made once and written into the stages: the partner, the cut axis, the
    blocks, and the slab's own n-D stage taken apart around the cut axis."""
    _block_bytes(monkeypatch, 1)
    comm = ht.get_comm()
    stages = fft_mod._stages("fft", ((0, None), (1, None), (2, None)), 0, None)
    assert stages == (("pencil", "fft", None, None), ("locals", "fftn", None, (1, 2), None))
    planned = fft_mod._planned(stages, comm, 0, (2 * P, 2 * P, 16), 2 * P, np.dtype(np.float32))
    assert planned == (("pencil", "fft", None, None, 2, 1, 4, ("locals", "fftn", None, (2,), None)), ("locals", "fftn", None, (1,), None))
    assert fft_mod._blocks(planned) == 4
    program = fft_mod._slab_program(comm, 0, 3, 2 * P, planned)
    txt = program.lower(jax.ShapeDtypeStruct((2 * P, 2 * P, 16), np.float32, sharding=comm.sharding(0))).compile().as_text()
    assert "all-gather" not in txt
    assert len(_re.findall(r" all-to-all\(", txt)) == 8
    assert all(f"/{scope}/" in txt for scope in ("fft.alltoall.in", "fft.split_axis", "fft.alltoall.out", "fft.local"))
    assert program.lower(jax.ShapeDtypeStruct((2 * P, 2 * P, 16), np.float32, sharding=comm.sharding(0))).as_text().count("optimization_barrier") == 3


def test_a_mesh_without_the_overlap_option_takes_one_block(default_engine, monkeypatch):
    """Where the mesh's compiler has no option that puts an exchange beside
    compute (the CPU's refuses the TPU's name: none is passed), cutting only
    costs: the plan is PR 31's one block whatever the slab's size."""
    _block_bytes(monkeypatch, 1, options={})
    comm = ht.get_comm()
    assert comm.overlap_compiler_options() == {}
    stages = fft_mod._stages("fft", ((0, None), (1, None), (2, None)), 0, None)
    planned = fft_mod._planned(stages, comm, 0, (2 * P, 2 * P, 16), 2 * P, np.dtype(np.float32))
    assert planned == (("pencil", "fft", None, None, 1, None, 1, None), stages[1])
    x = np.random.default_rng(29).standard_normal(CUBE)
    y, spans, calls, _ = _traced("fftn", x, 0, {})
    assert spans["ht.fft.fftn"].attrs["blocks"] == spans["fft.dispatch"].attrs["blocks"] == 1 and calls == 2
    np.testing.assert_allclose(y.numpy(), np.fft.fftn(x), atol=1e-9 * np.max(np.abs(np.fft.fftn(x))))


def test_the_real_communicator_offers_the_option_on_a_tpu_mesh_only():
    """``Communication.overlap_compiler_options``, unpatched, on the tests' forced-host mesh."""
    assert ht.get_comm().overlap_compiler_options() == {}
