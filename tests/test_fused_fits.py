"""A fit reads its table once (PR 36): ``statistics.mean_var`` (both moments
from shifted sums), ``statistics.min_max`` and the selection's neighbours'
pass, each ONE program over the input, held to numpy in float64.

The variance may be no worse than four times what the two-pass form
(``jnp.var`` in float32) loses on the same column, or 1e-5 of the variance,
whichever is larger; extrema and selected ranks are exact.  Every case runs
on one device, along a split axis with pad rows over the suite's eight host
devices, and through a waiting in-place store.  All on the CPU: results and
counts, never a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import dispatch, statistics
from heat_tpu.parallel.comm import Communication

ROWS, COLS = 40_003, 6  # no multiple of the eight devices: three pad rows
#: the rows ``_moments_fn`` takes its shift from
SAMPLED = [i * (ROWS // statistics._SHIFT_ROWS) for i in range(statistics._SHIFT_ROWS)]


def _column(kind: str, seed: int):
    """One column of ``ROWS`` float32 values, unit scale unless the kind says otherwise."""
    rng = np.random.default_rng([seed, sum(map(ord, kind))])
    z = rng.standard_normal(ROWS)
    if kind.startswith("offset"):
        z = z + float(kind[len("offset_"):])
    elif kind == "drift":
        z = z + np.linspace(-40.0, 60.0, ROWS)
    elif kind == "constant":
        z = np.full(ROWS, 3.25)
    elif kind == "sampled_outliers":
        z[SAMPLED] = 1e6
    elif kind == "one_sampled_outlier":
        z[SAMPLED[3]] = -1e6
    elif kind == "heavy_tails":
        z = z * np.abs(z) ** 3
    elif kind == "lognormal":
        z = np.exp(3.0 * z)
    elif kind == "sorted":
        z = np.sort(z * np.abs(z))
    return z.astype(np.float32)


KINDS = ("offset_1e0", "offset_1e3", "offset_1e6", "drift", "constant", "sampled_outliers", "one_sampled_outlier",
         "heavy_tails", "lognormal", "sorted")
LAYOUTS = ("one_device", "split_padded", "through_a_chain")


def _table(kind):
    return np.stack([_column(kind, seed) for seed in range(COLS)], axis=1)


@pytest.fixture()
def layout(request):
    """``make(a)`` -> (the DNDarray, the float32 values it stands for)."""
    name = request.param
    if name == "one_device":
        ht.use_comm(Communication(jax.devices()[:1]))

    def make(a):
        x = ht.array(a, split=0)
        if name != "through_a_chain":
            return x, a
        assert x._pad > 0
        # a deferred in-place store: the table now waits behind `x * 2 - 1`
        x *= 2
        x -= 1
        return x, a * a.dtype.type(2) - a.dtype.type(1)

    make.name = name
    try:
        yield make
    finally:
        ht.use_comm(ht.WORLD)


def _two_pass_loss(values, ref, ddof):
    return np.abs(np.asarray(jnp.var(jnp.asarray(values), axis=0, ddof=ddof), np.float64) - ref)


@pytest.mark.parametrize("ddof", [0, 1])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("layout", LAYOUTS, indirect=True)
def test_both_moments_from_one_read(layout, kind, ddof):
    x, values = layout(_table(kind))
    before = dispatch.cache_stats()
    mean, var = statistics.mean_var(x, axis=0, ddof=ddof)
    got_mean, got_var = mean.numpy().astype(np.float64), var.numpy().astype(np.float64)
    after = dispatch.cache_stats()
    # ONE program, and a waiting store goes on waiting
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["stores"] == before["stores"] and after["deferred_stores"] == before["deferred_stores"]
    wide = values.astype(np.float64)
    ref_mean, ref_var = wide.mean(axis=0), wide.var(axis=0, ddof=ddof)
    allowed = np.maximum(4.0 * _two_pass_loss(values, ref_var, ddof), 1e-5 * ref_var)
    assert np.all(np.abs(got_var - ref_var) <= allowed), (got_var, ref_var, allowed)
    # the mean as good as a float32 sum's: a few units in the last place of its own size, or of the spread's
    scale = np.maximum(np.abs(ref_mean), np.sqrt(ref_var))
    assert np.all(np.abs(got_mean - ref_mean) <= 4e-6 * scale + 1e-30), (got_mean, ref_mean)
    if kind == "constant":
        assert np.all(got_var == 0.0)  # exactly: `_guard_zero` keys on it
    # `ht.var` / `ht.std` are the same program's second result
    np.testing.assert_array_equal(ht.var(x, axis=0, ddof=ddof).numpy(), var.numpy())
    np.testing.assert_array_equal(ht.std(x, axis=0, ddof=ddof).numpy(), np.sqrt(var.numpy()))


@pytest.mark.parametrize("special", ["nan", "inf", "both_inf", "nan_in_the_sample", "inf_in_the_sample"])
@pytest.mark.parametrize("layout", LAYOUTS, indirect=True)
def test_nan_and_infinity_as_jnp_var_treats_them(layout, special):
    a = _table("offset_1e0")
    row = SAMPLED[2] if special.endswith("sample") else 11
    a[row, 1] = np.nan if special.startswith("nan") else np.inf
    if special == "both_inf":
        a[row + 1, 1] = -np.inf
    x, values = layout(a)
    mean, var = statistics.mean_var(x, axis=0)
    with np.errstate(invalid="ignore"):
        np.testing.assert_allclose(mean.numpy(), np.asarray(jnp.mean(jnp.asarray(values), axis=0)), rtol=1e-5)
        want = np.asarray(jnp.var(jnp.asarray(values), axis=0))
    assert np.isnan(want[1]) and np.isnan(var.numpy()[1])
    np.testing.assert_allclose(np.delete(var.numpy(), 1), np.delete(want, 1), rtol=1e-5)


@pytest.mark.parametrize("axis", [None, 0, 1, (0, 2), (1, 2)])
@pytest.mark.parametrize("split", [None, 0, 1, 2])
def test_variance_over_any_axes(split, axis):
    a = (np.random.default_rng(2).standard_normal((21, 13, 5)) * 3 + 1e3).astype(np.float32)
    x = ht.array(a, split=split)
    for keepdims in (False, True):
        mean, var = statistics.mean_var(x, axis=axis, ddof=1, keepdims=keepdims)
        wide = a.astype(np.float64)
        np.testing.assert_allclose(var.numpy(), wide.var(axis=axis, ddof=1, keepdims=keepdims), rtol=2e-5)
        np.testing.assert_allclose(mean.numpy(), wide.mean(axis=axis, keepdims=keepdims), rtol=1e-6)
        assert var.shape == wide.var(axis=axis, keepdims=keepdims).shape


@pytest.mark.parametrize("dtype", ["float64", "bfloat16", "int32", "complex64"])
def test_variance_of_other_types(dtype):
    a = np.random.default_rng(4).integers(-50, 50, (2003, 3))
    x = ht.array(a, split=0).astype(getattr(ht, dtype))
    got = ht.var(x, axis=0)
    want = np.asarray(x.numpy()).astype(np.complex128 if dtype == "complex64" else np.float64).var(axis=0)
    assert got.dtype == {"int32": ht.float32, "complex64": ht.float32}.get(dtype, getattr(ht, dtype))
    np.testing.assert_allclose(got.numpy().astype(np.float64), want, rtol=2e-2 if dtype == "bfloat16" else 1e-5)


@pytest.mark.parametrize("kind", ["offset_1e3", "heavy_tails", "constant", "with_nan", "int32"])
@pytest.mark.parametrize("layout", LAYOUTS, indirect=True)
def test_both_extrema_from_one_read(layout, kind):
    if kind == "with_nan":
        a = _table("drift")
        a[17, 2] = np.nan
    elif kind == "int32":
        a = np.random.default_rng(6).integers(-2 ** 29, 2 ** 29, (ROWS, COLS)).astype(np.int32)
    else:
        a = _table(kind)
    x, values = layout(a)
    before = dispatch.cache_stats()
    low, high = statistics.min_max(x, axis=0)
    got = low.numpy(), high.numpy()
    after = dispatch.cache_stats()
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["stores"] == before["stores"] and after["deferred_stores"] == before["deferred_stores"]
    # bit for bit what `min` and `max` give apart, and what numpy gives
    np.testing.assert_array_equal(got[0], ht.min(x, axis=0).numpy())
    np.testing.assert_array_equal(got[1], ht.max(x, axis=0).numpy())
    if kind != "with_nan" or layout.name == "one_device":  # the CPU mesh's all-reduce drops a NaN, for `ht.min` too
        np.testing.assert_array_equal(got[0], values.min(axis=0))
        np.testing.assert_array_equal(got[1], values.max(axis=0))
    assert low.dtype == high.dtype == x.dtype


@pytest.mark.parametrize("scaler", ["StandardScaler", "MinMaxScaler", "MaxAbsScaler"])
def test_a_fit_behind_a_waiting_store_launches_one_program_and_no_store(scaler):
    """``fit`` after a deferred ``copy=False`` transform reads the table
    THROUGH the waiting chain: one ``chain`` launch, no ``cast_store``, the
    store still waiting afterwards, and the attributes those of the
    transformed table."""
    a = _table("drift")
    x = ht.array(a, split=0)
    first = ht.preprocessing.MaxAbsScaler(copy=False).fit(x)
    assert first.transform(x) is x
    prev = telemetry.set_tracing(True)
    try:
        telemetry.clear_spans()
        before = dispatch.cache_stats()
        fitted = getattr(ht.preprocessing, scaler)().fit(x)
        after = dispatch.cache_stats()
        kinds = [r.attrs["kind"] for r in telemetry.get_spans() if r.name == "dispatch.launch"]
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
    assert kinds == ["chain"] and after["stores"] == before["stores"] and after["deferred_stores"] == before["deferred_stores"]
    scaled = (a / np.abs(a).max(axis=0)).astype(np.float64)
    if scaler == "StandardScaler":
        np.testing.assert_allclose(fitted.mean_.numpy(), scaled.mean(axis=0), rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(fitted.var_.numpy(), scaled.var(axis=0), rtol=1e-4)
    elif scaler == "MinMaxScaler":
        np.testing.assert_allclose(fitted.data_min_.numpy(), scaled.min(axis=0), rtol=1e-6)
        np.testing.assert_allclose(fitted.data_max_.numpy(), scaled.max(axis=0), rtol=1e-6)
    else:
        np.testing.assert_allclose(fitted.max_abs_.numpy(), 1.0, rtol=1e-6)
    np.testing.assert_allclose(x.numpy(), scaled, rtol=1e-6)  # the store ran at this read, and only now
    assert dispatch.cache_stats()["stores"] == after["stores"] + 1


@pytest.mark.parametrize("with_mean,with_std", [(True, True), (False, True), (True, False), (False, False)])
def test_standard_scaler_asks_for_what_it_needs(with_mean, with_std):
    a = _table("offset_1e3")
    x = ht.array(a, split=0)
    before = dispatch.cache_stats()["dispatches"]
    s = ht.preprocessing.StandardScaler(with_mean=with_mean, with_std=with_std).fit(x)
    assert dispatch.cache_stats()["dispatches"] - before == int(with_mean or with_std)
    assert (s.mean_ is not None) is with_mean and (s.var_ is not None) is with_std
    if with_mean:
        np.testing.assert_allclose(s.mean_.numpy(), a.astype(np.float64).mean(axis=0), rtol=1e-6)
    if with_std:
        np.testing.assert_allclose(s.var_.numpy(), a.astype(np.float64).var(axis=0), rtol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("layout", LAYOUTS[:2], indirect=True)
def test_the_neighbours_pass_selects_the_next_rank_bit_for_bit(layout, dtype, monkeypatch):
    """The rank and the next rank of every column against a full sort: ties
    that reach the next rank, ties that stop at the rank, both zeros, both
    infinities, negative values."""
    monkeypatch.setattr(statistics, "_SELECT_MIN_EXTENT", 8)
    a = np.round(_table("heavy_tails")[:4099], 1).astype(dtype)   # many duplicates
    a[:2050, 1] = 1.5
    a[10:20, 2], a[20:30, 2] = 0.0, -0.0
    a[5, 3], a[7, 3] = np.inf, -np.inf
    x, values = layout(a)
    ordered = np.sort(values, axis=0)
    for rank in (0, 1023, 2048, 2049, 4097):
        q = 100.0 * (rank + 0.5) / (len(a) - 1)   # between `rank` and `rank + 1`
        low = ht.percentile(x, q, axis=0, interpolation="lower").numpy()
        high = ht.percentile(x, q, axis=0, interpolation="higher").numpy()
        np.testing.assert_array_equal(low, ordered[rank])
        np.testing.assert_array_equal(high, ordered[rank + 1])
