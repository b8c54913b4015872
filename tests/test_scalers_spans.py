"""Spans and counters of the eager path the scalers' cell times
(``ht.preprocessing.*``, ``statistics.quantiles``; PR 33), and the
benchmark's four readers of them against a synthetic ring.  All on the CPU:
names, attributes, counts and containment, never a time.
"""

import os
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import dispatch
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402

SCALERS = ("StandardScaler", "MinMaxScaler", "MaxAbsScaler", "RobustScaler", "Normalizer")
#: the calls of one solve, in upstream's order
CALLS = [f"ht.preprocessing.{s}.{m}" for s in SCALERS[:4] for m in ("fit", "transform", "inverse_transform")] + [
    "ht.preprocessing.Normalizer.fit", "ht.preprocessing.Normalizer.transform"]


@pytest.fixture()
def one_device():
    ht.use_comm(Communication(jax.devices()[:1]))
    prev = telemetry.set_tracing(True)
    try:
        yield
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
        ht.use_comm(ht.WORLD)


def _upstream(x, copy):
    """benchmarks/cb/preprocessing.py's five functions, in its order."""
    for name in SCALERS[:4]:
        scaler = getattr(ht.preprocessing, name)(copy=copy)
        scaler.inverse_transform(scaler.fit_transform(x))
    ht.preprocessing.Normalizer(copy=copy).fit_transform(x)


@pytest.mark.parametrize("copy", [False, True])
def test_a_solve_leaves_its_root_spans(one_device, copy):
    """Fourteen root spans a solve, each with the table's shape and what the
    call launched.  Since PR 34 an in-place call defers its store, so the
    sums are a solve's and not a call's: nine deferrals, and the stores that
    ran (``RobustScaler.fit``'s read inside its root, the caller's read after
    the last) all donated."""
    x = ht.array(np.random.default_rng(0).standard_normal((600, 50)).astype(np.float32), split=0)
    _upstream(x, copy)  # warm
    x.larray_padded
    telemetry.clear_spans()
    before = dispatch.cache_stats()
    _upstream(x, copy)
    x.larray_padded  # the caller's read ends the solve, as the benchmark's `block_until_ready` does
    after = dispatch.cache_stats()
    step = {k: after[k] - before[k] for k in ("stores", "donations", "deferred_stores", "dispatches", "external_dispatches")}
    spans = telemetry.get_spans()
    roots = [r for r in spans if r.name.startswith("ht.preprocessing.")]
    assert [r.name for r in roots] == CALLS
    assert all(r.depth == 0 and r.attrs["rows"] == 600 and r.attrs["features"] == 50 and r.attrs["split"] == 0
               and r.attrs["copy"] is copy for r in roots)
    assert sum(r.attrs["deferred"] for r in roots) == (0 if copy else 9) == step["deferred_stores"]
    assert step["stores"] == step["donations"] == (0 if copy else 2) and step["stores"] <= 3
    forced = [r.name for r in roots if r.attrs["stores"]]
    assert forced == ([] if copy else ["ht.preprocessing.RobustScaler.fit"])  # the last store runs outside every root
    assert sum(r.attrs["donations"] for r in roots) == len(forced)
    for r in roots:
        applies = r.name.endswith("transform")
        assert r.attrs["deferred"] == int(applies and not copy), r
        assert r.attrs["inplace"] is (not copy and (applies or r.name in forced)), r
    launches = sum(r.attrs["launches"] for r in roots)
    assert launches == step["dispatches"] + step["external_dispatches"] - (0 if copy else 1)
    assert launches >= len(forced) + 5  # every store is a launch, and so is every fit's ONE reduction (PR 36)
    fits = {r.name.split(".")[2]: r.attrs["launches"] for r in roots if r.name.endswith(".fit")}
    assert fits == {"StandardScaler": 1, "MinMaxScaler": 1, "MaxAbsScaler": 1, "RobustScaler": 2 - copy, "Normalizer": 0}
    # the quantiles' span, and since PR 35 one `dispatch.launch` a program (tests/test_dispatch_spans.py): the ring holds 4,096
    assert len([r for r in spans if r.name != "dispatch.launch"]) <= len(roots) + 3
    inner = [r for r in spans if r.name == "statistics.quantiles"]
    fit = next(r for r in roots if r.name == "ht.preprocessing.RobustScaler.fit")
    assert len(inner) == 1 and inner[0].depth == 1 and inner[0].attrs["q"] == (50.0, 25.0, 75.0)
    assert inner[0].attrs["route"] == "sort" and inner[0].attrs["passes"] == 1 and inner[0].attrs["launches"] == 1
    assert fit.start_ns <= inner[0].start_ns and inner[0].start_ns + inner[0].duration_ns <= fit.start_ns + fit.duration_ns


def test_tracing_off_changes_no_result(one_device):
    a = np.random.default_rng(1).standard_normal((300, 8)).astype(np.float32)
    x, y = ht.array(a, split=0), ht.array(a, split=0)
    _upstream(x, False)
    telemetry.set_tracing(False)
    telemetry.clear_spans()
    _upstream(y, False)
    assert telemetry.get_spans() == [] and np.array_equal(x.numpy(), y.numpy())


def test_the_programs_carry_their_scopes(one_device):
    """``scaler.fit``, ``scaler.apply`` and ``quantile.count`` name the
    operations of the programs traced under them."""
    from heat_tpu.core import statistics

    lowered = statistics._select_program.lower(
        jax.ShapeDtypeStruct((64, 3), np.float32), axis=0, lows=(31,), with_high=True, plan=((0, True, 0.5),), method="linear",
        keepdims=False, scalar_q=False, n_true=64)
    assert "quantile.count" in lowered.as_text(debug_info=True)
    x = ht.array(np.ones((32, 4), np.float32), split=0)
    scaler = ht.preprocessing.MaxAbsScaler(copy=False)

    def scoped(f):
        return jax.make_jaxpr(lambda a: f(ht.DNDarray(a, x.shape, x.dtype, 0, x.device, x.comm)).larray_padded)(
            x.larray_padded).pretty_print(name_stack=True)

    assert "scaler.fit" in scoped(lambda t: scaler.fit(t).max_abs_)
    scaler.fit(x)
    # a deferred store, like a copy=True chain, is traced where it is first read: by the caller, under no
    # scope of the scalers, or inside the fit that reads it
    assert "scaler.apply" not in scoped(scaler.transform)
    assert "scaler.fit" in scoped(lambda t: ht.preprocessing.RobustScaler().fit(scaler.transform(t)).center_)


# ------------------------------------------------------------------- the readers
def _ring(solves, warmup=2, counters=True, quantiles=True, copies=0):
    """A ring as a run leaves it: ``warmup`` solves, then the window's, every
    call 1 ms long and 10 ms apart, a store a transform."""
    telemetry.clear_spans()
    t = 0
    for i in range(warmup + solves):
        for name in CALLS:
            t += 10_000_000
            applies = name.endswith("transform")
            attrs = dict(launches=5 if i < warmup else (1 if applies else 2), stores=int(applies),
                         donations=int(applies) - (copies if name.endswith("Normalizer.transform") else 0)) if counters else {}
            if name == "ht.preprocessing.RobustScaler.fit" and quantiles:
                telemetry.record_span("statistics.quantiles", t + 100_000, 500_000, route="select", passes=17, launches=1)
            telemetry.record_span(name, t, 5_000_000 if i < warmup else 1_000_000, rows=1, **attrs)


#: (reader, case) -> (what fills the ring, the reading wanted; None: nothing read, with a note)
READER_CASES = {
    ("scalers_host_ms", "read"): (lambda: _ring(4), 14.0),
    ("scalers_host_ms", "ring_wrapped"): (lambda: _ring(3, warmup=0), None),
    ("scalers_host_ms", "tracing_off"): (telemetry.clear_spans, None),
    ("scalers_host_ms", "a_program_without_the_counters"): (lambda: _ring(4, counters=False), None),
    ("scalers_launches", "read"): (lambda: _ring(4), 5 * 2 + 9 * 1),
    ("scalers_launches", "no_such_span"): (telemetry.clear_spans, None),
    ("scalers_copies", "read_none"): (lambda: _ring(4), 0.0),
    ("scalers_copies", "read_one_a_solve"): (lambda: _ring(4, copies=1), 1.0),
    ("scalers_copies", "no_such_span"): (telemetry.clear_spans, None),
    ("quantile_passes", "read"): (lambda: _ring(4), 17.0),
    ("quantile_passes", "no_quantiles_span"): (lambda: _ring(4, quantiles=False), None),
    ("quantile_passes", "no_such_span"): (telemetry.clear_spans, None),
}


@pytest.mark.parametrize("reader,case", sorted(READER_CASES))
def test_scalers_layer_metric_readers(one_device, reader, case):
    fill, want = READER_CASES[(reader, case)]
    fill()
    run = {"trace": {"top_ops": [], "busy_s": 1.0}, "solves": 4, "window_s": 2.0, "notes": {}}
    got = load_py("layer_metrics", reader).read(run)
    if want is None:
        assert got is None and reader in run["notes"]
    else:
        assert got == pytest.approx(want) and run["notes"] == {}
