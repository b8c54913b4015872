"""Buffer-donation tests (ISSUE 1 tentpole piece 2).

In-place ops (``resplit_``, ``out=`` stores, ``__iadd__``-style dunders)
donate the target's dead backing buffer to the compiled program so XLA
can reuse the allocation.  Two properties are pinned here:

* in-place paths do not GROW the live device-buffer population
  (``jax.live_arrays()`` before/after on the CPU backend);
* donation NEVER fires when the buffer is shared — another DNDarray,
  a pending chain elsewhere, or a user-held ``larray_padded`` — and the
  sharing holder stays readable afterwards.
"""

import gc
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import dispatch
from heat_tpu.parallel.comm import Communication


def _live_count() -> int:
    gc.collect()
    return len(jax.live_arrays())


def test_iadd_does_not_grow_live_buffers():
    x = ht.arange(64, split=0).astype(ht.float32)
    y = ht.ones(64, split=0)
    x += y  # warm the executable
    before = _live_count()
    for _ in range(10):
        x += y
    after = _live_count()
    assert after <= before, f"live buffers grew {before} -> {after}"
    np.testing.assert_allclose(x.numpy(), np.arange(64) + 11.0, rtol=1e-6)


def test_resplit_does_not_grow_live_buffers():
    x = ht.arange(65, split=0).astype(ht.float32)  # indivisible: padded
    want = x.numpy().copy()
    x.resplit_(None)  # warm both directions
    x.resplit_(0)
    before = _live_count()
    for _ in range(5):
        x.resplit_(None)
        x.resplit_(0)
    after = _live_count()
    assert after <= before, f"live buffers grew {before} -> {after}"
    np.testing.assert_allclose(x.numpy(), want, rtol=1e-6)


def test_out_store_does_not_grow_live_buffers():
    a = ht.arange(64, split=0).astype(ht.float32)
    b = ht.full((64,), 2.0, split=0)
    out = ht.zeros(64, split=0)
    ht.mul(a, b, out=out)  # warm
    before = _live_count()
    for _ in range(10):
        ht.mul(a, b, out=out)
        ht.add(a, b, out=out)
    after = _live_count()
    assert after <= before, f"live buffers grew {before} -> {after}"
    np.testing.assert_allclose(out.numpy(), np.arange(64) + 2.0, rtol=1e-6)


def test_iadd_donates_when_unshared():
    x = ht.arange(64, split=0).astype(ht.float32)
    x += 1.0  # warm
    x.larray_padded
    dispatch.reset_stats()
    x += 1.0
    x.larray_padded  # the store waits for its first reader (PR 34)
    if dispatch._DONATE_ENABLED:
        assert dispatch.cache_stats()["donations"] >= 1
    np.testing.assert_allclose(x.numpy(), np.arange(64) + 2.0, rtol=1e-6)


def test_no_donation_when_chain_references_buffer():
    """tmp = x + y keeps x's buffer as a chain leaf: x += tmp must NOT
    donate, and tmp must stay readable afterwards."""
    x = ht.arange(32, split=0).astype(ht.float32)
    y = ht.ones(32, split=0)
    tmp = x + y  # pending chain, leaf = x's buffer
    dispatch.reset_stats()
    x += tmp
    if dispatch.fusion_enabled():
        # with fusion off tmp is already concrete, so donating x's old
        # buffer is safe and allowed — the refusal only applies to a
        # LIVE chain that still references the buffer
        assert dispatch.cache_stats()["donations"] == 0
    np.testing.assert_allclose(tmp.numpy(), np.arange(32) + 1.0, rtol=1e-6)
    np.testing.assert_allclose(x.numpy(), 2 * np.arange(32) + 1.0, rtol=1e-6)


def test_no_donation_when_user_holds_buffer():
    x = ht.arange(32, split=0).astype(ht.float32)
    held = x.larray_padded
    dispatch.reset_stats()
    x += 1.0
    assert float(x.numpy()[5]) == 6.0
    assert dispatch.cache_stats()["donations"] == 0
    assert float(np.asarray(held)[5]) == 5.0  # old buffer untouched


def test_no_donation_when_backing_is_shared():
    x = ht.arange(32, split=0).astype(ht.float32)
    alias = x.resplit(0)  # same-axis resplit shares the backing buffer
    dispatch.reset_stats()
    x += 1.0
    np.testing.assert_allclose(x.numpy(), np.arange(32) + 1.0, rtol=1e-6)
    assert dispatch.cache_stats()["donations"] == 0
    np.testing.assert_allclose(alias.numpy(), np.arange(32), rtol=1e-6)


def test_no_donation_on_resplit_with_shared_backing():
    x = ht.arange(32, split=0).astype(ht.float32)
    alias = x.resplit(0)
    dispatch.reset_stats()
    x.resplit_(None)
    assert dispatch.cache_stats()["donations"] == 0
    np.testing.assert_allclose(alias.numpy(), np.arange(32), rtol=1e-6)


def test_inplace_loop_values_stay_correct():
    """The full ML-loop shape: repeated donating += with a warm cache."""
    w = ht.zeros(128, split=0)
    g = ht.ones(128, split=0)
    for _ in range(25):
        w += g * 0.5
    np.testing.assert_allclose(w.numpy(), 12.5, rtol=1e-5)


# ----------------------------------------------------------------------
# the deferred in-place store (PR 34): `_iop` hands the pending chain to its
# target where the chain reads the target's buffer and no other of its size;
# the first reader runs all of it as ONE program through the donating store
# ----------------------------------------------------------------------
ROWS, COLS = 1003, 50  # ragged over four devices
SCALERS = ("StandardScaler", "MinMaxScaler", "MaxAbsScaler", "RobustScaler", "Normalizer")


@pytest.fixture(params=[1, 4], ids=["one_device", "four_devices"])
def mesh(request):
    """One device, and four forced host devices as the four-chip host has."""
    ht.use_comm(Communication(jax.devices()[: request.param]))
    try:
        yield request.param
    finally:
        ht.use_comm(ht.WORLD)


def _table(seed=5) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((ROWS, COLS)) * np.geomspace(0.25, 4.0, COLS) + rng.uniform(-2, 2, COLS)).astype(np.float32)


def _address(x) -> int:
    return x.larray_padded.addressable_shards[0].data.unsafe_buffer_pointer()


def _steps():
    s = dispatch.cache_stats()
    return tuple(s[k] for k in ("dispatches", "stores", "donations", "deferred_stores"))


def test_a_deferred_store_launches_nothing_and_its_reader_pays_once(mesh):
    if not (dispatch.fusion_enabled() and dispatch._DONATE_ENABLED):
        pytest.skip("fusion or donation is off")
    a = _table()
    x = ht.array(a, split=0)
    row = ht.array(a.mean(0))
    for warm in range(2):
        x.larray_padded
        home = _address(x)
        dispatch.reset_stats()
        x *= 2.0
        x -= row
        x /= 3.0
        assert _steps() == (0, 0, 0, 3) and x._pending is not None
        total = x.sum()  # a reduction folds the chain into its own program and leaves it pending
        assert _steps() == (1, 0, 0, 3) and x._pending is not None
        x.larray_padded
        assert _steps() == (2, 1, 1, 3) and x._pending is None
        # the output aliases the old buffer (the first round's may be the host's own memory, put on
        # the CPU device without a copy: the backend cannot alias that, and says nothing)
        assert not warm or _address(x) == home
    want = a
    for _ in range(2):
        want = (want * np.float32(2.0) - a.mean(0)) / np.float32(3.0)
    np.testing.assert_allclose(x.numpy(), want, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(float(total), want.sum(dtype=np.float64), rtol=1e-4)


def _scaler_pair(name):
    def run(x, read):
        scaler = getattr(ht.preprocessing, name)(copy=False)
        read(scaler.fit_transform(x))
        if name != "Normalizer":
            read(scaler.inverse_transform(x))
        return [v.numpy() for k, v in sorted(vars(scaler).items()) if k.endswith("_") and isinstance(v, ht.DNDarray)]
    return run


def _dunders(x, read):
    x *= 1.7
    read(x)
    x += 0.3
    read(x)
    x /= 1.1
    read(x)
    x /= 0.7  # `(x / a) / b`: inside one program the simplifier would make it `x / (a * b)`
    read(x)
    return []


def _rows(x, read):
    m, s = ht.array(np.linspace(-1, 1, COLS, dtype=np.float32)), ht.array(np.geomspace(0.5, 3, COLS).astype(np.float32))
    x -= m
    read(x)
    x /= s
    read(x)
    x *= s
    read(x)
    x += m
    read(x)
    return []


BIT_CASES = {**{name: _scaler_pair(name) for name in SCALERS}, "x*=a;x+=b;x/=c;x/=d": _dunders, "rows": _rows}


@pytest.mark.parametrize("case", sorted(BIT_CASES))
def test_a_deferred_chain_is_bit_for_bit_the_stores_one_by_one(mesh, case):
    """The reference reads the table after every in-place call, which runs
    each store as a program of its own: PR 33's stores.  The deferred form
    reads it at the end.  Table and fitted attributes: not a bit apart,
    although one program holds what were up to eight (a product with a
    run-time 1 marks every deferred store, `dispatch._stored`)."""
    a = _table(11)
    run = BIT_CASES[case]
    eager, deferred = ht.array(a, split=0), ht.array(a, split=0)
    for _ in range(2):  # the second round works on the first's table, as the benchmark's loop does
        want = run(eager, lambda t: t.larray_padded)
        dispatch.reset_stats()
        got = run(deferred, lambda t: None)
        if dispatch.fusion_enabled():
            assert dispatch.cache_stats()["deferred_stores"] >= 1
        assert len(got) == len(want) and all(np.array_equal(g, w) for g, w in zip(got, want))
        assert np.array_equal(deferred.numpy(), eager.numpy())
    assert np.isfinite(eager.numpy()).all() and not np.array_equal(eager.numpy(), a)


def test_a_long_in_place_chain_never_holds_two_generations(mesh):
    """Forty in-place operations: a chain at the depth limit is not taken
    on; that store runs at once, the waiting chain inside it, into the
    array's own buffer (``make_node`` never sees a deferred chain at the
    limit, which it would cut into a fresh buffer).  Every store donates,
    the address stays and the population of live buffers does not grow."""
    if not (dispatch.fusion_enabled() and dispatch._DONATE_ENABLED):
        pytest.skip("fusion or donation is off")
    a = _table()
    x = ht.array(a, split=0)

    def forty(x):
        for i in range(20):
            x *= 1.01
            x += 0.01

    forty(x)
    x.larray_padded  # warm
    home, before = _address(x), _live_count()
    dispatch.reset_stats()
    forty(x)
    x.larray_padded
    s = dispatch.cache_stats()
    now = 40 // dispatch.FUSION_DEPTH  # the operation that would take the chain to the limit stores at once
    assert s["deferred_stores"] == 40 - now and s["stores"] == s["donations"] == s["dispatches"] == now + 1
    assert _address(x) == home and _live_count() <= before
    want = a.astype(np.float64)
    for _ in range(40):  # twenty pairs in the warm round, twenty in the counted one
        want = want * 1.01 + 0.01
    np.testing.assert_allclose(x.numpy(), want, rtol=3e-5, atol=3e-5)


def _full_size_operand(x, a):
    y = ht.array(a, split=0)
    dispatch.reset_stats()
    x += y
    return (1, 0, 1), a + a


def _full_size_operand_on_a_deferred_target(x, a):
    y = ht.array(a, split=0)
    x *= 2.0
    dispatch.reset_stats()
    x += y  # the waiting chain and this store are ONE program, into x's buffer
    return (1, 0, 1), a * np.float32(2.0) + a


def _planar_target(x, a):
    z = ht.DNDarray.from_planar(x.larray_padded, x.larray_padded, x.shape, x.split, x.device, x.comm)
    dispatch.reset_stats()
    z *= 2.0
    got = dispatch.cache_stats()
    np.testing.assert_allclose(z.numpy(), (a + 1j * a) * 2.0, rtol=1e-6)
    assert got["deferred_stores"] == 0
    return None, a


def _complex_target(x, a):
    z = ht.array(a.astype(np.complex64) * (1 + 1j), split=0)
    dispatch.reset_stats()
    z *= 2.0
    assert dispatch.cache_stats()["deferred_stores"] == 0 and z._pending is None
    np.testing.assert_allclose(z.numpy(), a * (2 + 2j), rtol=1e-6)
    return None, a


def _out_onto_a_deferred_operand(x, a):
    x *= 2.0
    dispatch.reset_stats()
    ht.add(x, 1.0, out=x)  # an `out=` store runs at once, as ever (with the target an operand it never donated)
    return (1, 0, 0), a * np.float32(2.0) + np.float32(1.0)


NOW_CASES = {f.__name__.strip("_"): f for f in (
    _full_size_operand, _full_size_operand_on_a_deferred_target, _planar_target, _complex_target, _out_onto_a_deferred_operand)}


@pytest.mark.parametrize("case", sorted(NOW_CASES))
def test_a_store_that_cannot_wait_runs_at_once_as_before(mesh, case):
    a = _table()
    x = ht.array(a, split=0)
    counts, want = NOW_CASES[case](x, a)
    if counts is not None:
        s = dispatch.cache_stats()
        assert (s["stores"], s["deferred_stores"]) == counts[:2] and s["dispatches"] == 1 and x._pending is None
        if dispatch._DONATE_ENABLED and dispatch.fusion_enabled():
            assert s["donations"] == counts[2]
    np.testing.assert_allclose(x.numpy(), want, rtol=1e-6)


@pytest.mark.parametrize("holder", ["second_dndarray", "held_buffer", "an_array_built_on_the_chain"])
def test_a_deferred_store_on_a_shared_buffer_does_not_donate(mesh, holder):
    a = _table()
    x = ht.array(a, split=0)
    held = x.larray_padded
    other = x.resplit(0) if holder == "second_dndarray" else None
    dispatch.reset_stats()
    x *= 2.0
    if holder == "an_array_built_on_the_chain":
        del held
        other = x + 1.0  # its chain holds x's, and with it the buffer
    np.testing.assert_allclose(x.numpy(), a * 2.0, rtol=1e-6)
    s = dispatch.cache_stats()
    assert s["stores"] == 1 and s["donations"] == 0
    if holder == "an_array_built_on_the_chain":
        np.testing.assert_allclose(other.numpy(), a * 2.0 + 1.0, rtol=1e-6)
    else:
        assert np.array_equal(np.asarray(held)[:ROWS], a)
        if other is not None:
            assert np.array_equal(other.numpy(), a)


@pytest.mark.parametrize("case,error", [("shape", ValueError), ("cast", TypeError)])
def test_iop_still_raises_at_the_call(mesh, case, error):
    x = ht.array(np.arange(40, dtype=np.int32).reshape(10, 4), split=0)
    x *= 2  # a chain is waiting: the checks come before it
    with pytest.raises(error):
        if case == "shape":
            x += ht.ones((3, 10, 4))
        else:
            x += 1.5
    assert np.array_equal(x.numpy(), np.arange(40).reshape(10, 4) * 2)


def test_fusion_off_means_no_deferred_store():
    """``HEAT_TPU_FUSION=0`` already means "no pending chains": nothing is
    deferred, every store runs where it is asked for."""
    code = (
        "import numpy as np, heat_tpu as ht\n"
        "from heat_tpu.core import dispatch\n"
        "x = ht.array(np.ones((64, 4), np.float32), split=0)\n"
        "x *= 2.0; x += 1.0\n"
        "s = dispatch.cache_stats()\n"
        "assert s['deferred_stores'] == 0 and s['stores'] == 2 and x._pending is None, s\n"
        "assert float(x.numpy()[3, 1]) == 3.0\n"
    )
    env = dict(os.environ, HEAT_TPU_FUSION="0", JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert done.returncode == 0, done.stderr[-2000:]
