"""The five scalers against a plain reference, and ``copy=False`` pinned
(PR 33).

Three layers, each held to the one under it: ``numpy`` in float64 here, the
benchmark's plain reference (``chipbench/drivers/scalers_inplace.py``, which
imports nothing of the program), and the program.  ``copy=False`` means what
upstream means by it: the call returns its input object with the result in
its buffer, through the library's one in-place store, the old buffer donated
where it is provably unshared; ``copy=True`` leaves the input bit for bit.
"""

import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import dispatch
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_json, load_py  # noqa: E402

SCALERS = ("StandardScaler", "MinMaxScaler", "MaxAbsScaler", "RobustScaler", "Normalizer")
ROWS, COLS = 1003, 50  # ragged over four and over eight devices


@pytest.fixture(scope="module")
def driver():
    return load_py("drivers", "scalers_inplace")


@pytest.fixture()
def four_devices():
    """Four forced host devices, as the cell's mesh would be on a four-chip host."""
    ht.use_comm(Communication(jax.devices()[:4]))
    try:
        yield
    finally:
        ht.use_comm(ht.WORLD)


def _table(kind: str) -> np.ndarray:
    """Columns of different location, scale and tail; ``constant`` plants a
    column of one value (guarded scales), ``ties`` ties that straddle the
    quartiles."""
    rng = np.random.default_rng(5)
    a = (rng.standard_normal((ROWS, COLS)) * np.geomspace(0.25, 4.0, COLS) + rng.uniform(-2, 2, COLS)).astype(np.float32)
    a[:, 1::4] = np.exp(a[:, 1::4] * 0.25)
    if kind == "constant":
        a[:, 7] = 2.5
    if kind == "ties":
        a[:400, 3] = -1.0
        a[400:800, 3] = 0.5
        a[::2, 11] = np.round(a[::2, 11])
    return a


def _numpy_scaled(name: str, a: np.ndarray) -> np.ndarray:
    """The scaler by its definition, in float64."""
    a = a.astype(np.float64)
    one = lambda v: np.where(v == 0, 1.0, v)  # noqa: E731
    if name == "StandardScaler":
        return (a - a.mean(0)) / np.sqrt(one(a.var(0)))
    if name == "MinMaxScaler":
        return (a - a.min(0)) / one(a.max(0) - a.min(0))
    if name == "MaxAbsScaler":
        return a / one(np.abs(a).max(0))
    if name == "RobustScaler":
        q = np.percentile(a, [25, 50, 75], axis=0)
        return (a - q[1]) / one(q[2] - q[0])
    return a / one(np.sqrt((a * a).sum(1, keepdims=True)))


# ------------------------------------------------------- the reference against numpy
@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_the_reference_against_numpy(driver, seed):
    """The driver's reference (statistics of the seeded table after one
    Normalizer, block by block) against numpy in float64 on the same table."""
    rows, cols = 4096, 50
    p = driver._params(seed, cols)
    x0 = np.asarray(driver._block(p, 0, rows, rows), np.float64)
    assert np.array_equal(np.asarray(driver._block(p, 1024, 512, rows)), x0[1024:1536].astype(np.float32))  # any block again
    x1 = x0 / np.sqrt((x0 * x0).sum(1, keepdims=True))
    ref = driver.reference({"p": p, "rows": rows, "nb": 4, "sub": 2})
    np.testing.assert_allclose(ref["mean"], x1.mean(0), atol=2e-7)
    np.testing.assert_allclose(ref["var"], x1.var(0), rtol=2e-5)
    np.testing.assert_allclose(ref["min"], x1.min(0), atol=2e-7)
    np.testing.assert_allclose(ref["max"], x1.max(0), atol=2e-7)
    np.testing.assert_allclose(ref["q"], np.percentile(x1, driver.QUANTILES, axis=0), atol=3e-7)
    # the generator: different columns, a drift along the rows that halves cannot hide
    half = x1[: rows // 2]
    assert np.all(np.abs(half.mean(0) - x1.mean(0)) / x1.std(0) > 1e-2)
    assert np.ptp(x0.std(0)) > 1.0 and np.ptp(x0.mean(0)) > 1.0


def test_the_work_model(driver):
    cfg = load_json("configs", "scalers-inplace.json")
    work = driver.work(cfg)
    assert work == {"bytes": 14 * 2 ** 25 * 50 * 4, "operations": 28 * 2 ** 25 * 50}
    assert work["bytes"] == 93_952_409_600
    assert driver.work(cfg, 1000)["bytes"] == 14 * 1000 * 50 * 4


# ------------------------------------------------------- the program against the definitions
@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("copy", [True, False])
@pytest.mark.parametrize("name", SCALERS)
def test_scaler_against_its_definition(four_devices, name, copy, split):
    """Ragged rows, a constant column and ties: forward against the
    definition in float64, the inverse back to the input; ``copy=False``
    returns the object it was given, ``copy=True`` leaves it bit for bit."""
    a = _table("constant" if name != "RobustScaler" else "ties")
    x = ht.array(a, split=split)
    scaler = getattr(ht.preprocessing, name)(copy=copy)
    y = scaler.fit_transform(x)
    assert (y is x) == (not copy) and y.split == split and y.shape == (ROWS, COLS) and y.dtype == ht.float32
    np.testing.assert_allclose(y.numpy(), _numpy_scaled(name, a), rtol=3e-5, atol=3e-6)
    if copy:
        assert np.array_equal(x.numpy(), a)
    if name != "Normalizer":
        z = scaler.inverse_transform(y)
        assert (z is y) == (not copy)
        np.testing.assert_allclose(z.numpy(), a, rtol=3e-5, atol=3e-5)
        if copy:
            np.testing.assert_allclose(y.numpy(), _numpy_scaled(name, a), rtol=3e-5, atol=3e-6)


@pytest.mark.parametrize("norm", ["l1", "max"])
def test_normalizer_norms_in_place(four_devices, norm):
    a = _table("constant")
    a[5] = 0.0  # a row of zeros stays what it is
    x = ht.array(a, split=0)
    assert ht.preprocessing.Normalizer(norm=norm, copy=False).fit_transform(x) is x
    n = np.abs(a).sum(1, keepdims=True) if norm == "l1" else np.abs(a).max(1, keepdims=True)
    np.testing.assert_allclose(x.numpy(), a / np.where(n == 0, 1, n), rtol=3e-6)


@pytest.mark.parametrize("name", SCALERS)
def test_fitted_attributes_against_the_reference(four_devices, driver, name):
    """Every fitted attribute against the driver's ``_expected`` (the
    attributes by their definitions from float64 statistics)."""
    a = _table("ties")
    a64 = a.astype(np.float64)
    ref = {"mean": a64.mean(0), "var": a64.var(0), "min": a64.min(0), "max": a64.max(0),
           "q": np.percentile(a64, driver.QUANTILES, axis=0)}
    scaler = getattr(ht.preprocessing, name)().fit(ht.array(a, split=0))
    got = {f"{name}.{k}": v.numpy() for k, v in vars(scaler).items() if k.endswith("_") and isinstance(v, ht.DNDarray)}
    want = {k: v for k, v in driver._expected(ref).items() if k.startswith(name + ".")}
    assert set(got) == set(want)
    for key, (value, scale) in want.items():
        assert np.max(np.abs(got[key] - value) / scale) < 2e-5, key


# ------------------------------------------------------- copy=False is in place
def _live_count() -> int:
    gc.collect()
    return len(jax.live_arrays())


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("name", SCALERS)
def test_copy_false_donates_the_old_buffer(name, split):
    """Proved as ``test_dispatch_donation.py`` proves it, without holding the
    buffer: every call defers its store (PR 34), the one store that the
    caller's read runs is counted as a donation, the population of live
    device buffers does not grow, and the root span says ``inplace``."""
    if not dispatch._DONATE_ENABLED:
        pytest.skip("donation is off")
    x = ht.array(_table("constant"), split=split)

    def upstream():
        scaler = getattr(ht.preprocessing, name)(copy=False)
        y = scaler.fit_transform(x)
        return scaler.inverse_transform(y) if name != "Normalizer" else y

    for _ in range(2):  # warm the executables
        upstream()
        x.larray_padded
    prev = telemetry.set_tracing(True)
    try:
        telemetry.clear_spans()
        dispatch.reset_stats()
        before = _live_count()
        assert upstream() is x
        calls = 1 if name == "Normalizer" else 2
        assert dispatch.cache_stats()["stores"] == 0 and dispatch.cache_stats()["deferred_stores"] == calls
        x.larray_padded
        stats = dispatch.cache_stats()
        assert stats["stores"] == stats["donations"] == 1 and stats["deferred_stores"] == calls
        assert _live_count() <= before
        applied = [r for r in telemetry.get_spans() if r.name.endswith(("transform",)) and r.name.startswith("ht.preprocessing")]
        assert len(applied) == calls and all(r.attrs["inplace"] and r.attrs["deferred"] == 1
                                             and r.attrs["stores"] == r.attrs["donations"] == 0 for r in applied)
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()


@pytest.mark.parametrize("holder", ["second_dndarray", "held_buffer"])
@pytest.mark.parametrize("name", ["StandardScaler", "Normalizer"])
def test_a_shared_buffer_is_not_donated(name, holder):
    """A second ``DNDarray`` on the buffer, or a held ``larray_padded``: the
    result is still right, the holder stays readable and the store, where
    the reader runs it, does not donate (the call's span can only say that
    it deferred one)."""
    a = _table("constant")
    x = ht.array(a, split=0)
    scaler = getattr(ht.preprocessing, name)(copy=False).fit(x)
    held = x.larray_padded
    other = ht.DNDarray(held, x.shape, x.dtype, x.split, x.device, x.comm) if holder == "second_dndarray" else None
    prev = telemetry.set_tracing(True)
    try:
        telemetry.clear_spans()
        assert scaler.transform(x) is x
        span = [r for r in telemetry.get_spans() if r.name == f"ht.preprocessing.{name}.transform"][-1]
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
    assert span.attrs["deferred"] == 1 and span.attrs["stores"] == 0 and span.attrs["donations"] == 0
    dispatch.reset_stats()
    np.testing.assert_allclose(x.numpy(), _numpy_scaled(name, a), rtol=3e-5, atol=3e-6)
    assert dispatch.cache_stats()["stores"] == 1 and dispatch.cache_stats()["donations"] == 0
    assert np.array_equal(np.asarray(held)[:ROWS], a)
    if other is not None:
        assert np.array_equal(other.numpy(), a)


@pytest.mark.parametrize("name", SCALERS)
def test_an_integer_table_cannot_be_written_in_place(name):
    """The result is float32 and the input is not: the store's cast check
    raises, as upstream's does; with ``copy=True`` the same call succeeds."""
    x = ht.array(np.arange(40, dtype=np.int32).reshape(10, 4) - 7, split=0)
    with pytest.raises(TypeError, match="in-place"):
        getattr(ht.preprocessing, name)(copy=False).fit_transform(x)
    assert getattr(ht.preprocessing, name)(copy=True).fit_transform(x).dtype == ht.float32
