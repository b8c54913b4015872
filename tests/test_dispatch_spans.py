"""The ``dispatch.launch`` span (PR 35): one a program through
``core/dispatch.py::_run``, opened where an entrance starts to decide and
ended at the return of the enqueue, and the benchmark's three readers of it
against a synthetic ring.  All on the CPU: names, attributes, counts and
containment, never a time.
"""

import json
import os
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import resilience as rz
from heat_tpu import telemetry
from heat_tpu.core import dispatch
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_json, load_py  # noqa: E402

LAUNCH = "dispatch.launch"
#: what every launch span carries, and nothing else but ``fallback`` / ``error``
ATTRS = {"kind": str, "ops": int, "fresh": bool, "store": bool, "donated": bool, "folded": int}


@pytest.fixture()
def one_device():
    ht.use_comm(Communication(jax.devices()[:1]))
    prev = telemetry.set_tracing(True)
    telemetry.clear_spans()
    try:
        yield
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
        ht.use_comm(ht.WORLD)


def _launches():
    return [r for r in telemetry.get_spans() if r.name == LAUNCH]


def _end(rec):
    return rec.start_ns + rec.duration_ns


def _table(rows=64, cols=4, seed=0):
    return ht.array(np.random.default_rng(seed).standard_normal((rows, cols)).astype(np.float32), split=0)


# ------------------------------------------------------------------- one span a program
def _ready(*arrays):
    for a in arrays:
        a.larray_padded
    return arrays


def _iadd(x, y):
    x += y  # reads another buffer of its size: it cannot wait, and runs here


#: entrance -> (operands made ready outside the count, what goes through the entrance, the span's kind)
ENTRANCES = {
    "materialize": (lambda: _ready(_table()), lambda x: ((x + 1.0) * 2.0).larray_padded, "expr"),
    "chain_apply": (lambda: _ready(_table()), lambda x: ((x + 1.0) * 2.0).sum(), "chain"),
    "eager_apply": (lambda: [a.larray_padded for a in (_table(), _table(seed=1))],
                    lambda a, b: dispatch.eager_apply(jnp.add, (a, b)), "apply"),
    "cast_store": (lambda: _ready(_table(), _table(seed=1)), _iadd, "cast_store"),
    "repad": (lambda: _ready(_table(rows=65)), lambda x: x.resplit_(1), "repad"),
}
#: the operations fused into each entrance's one program (the chain's two and the sum; the sum and the cast)
OPS = {"materialize": 2, "chain_apply": 3, "eager_apply": 1, "cast_store": 2, "repad": 1}


@pytest.mark.parametrize("entrance", sorted(ENTRANCES))
@pytest.mark.parametrize("warm", [False, True], ids=["miss", "hit"])
def test_each_entrance_leaves_one_span_a_program(one_device, entrance, warm):
    """Every program through ``_run`` leaves exactly one span, with the
    entrance's ``kind`` and the ``ops`` that ``fused_ops`` steps by; a cache
    miss is ``fresh`` and holds its ``dispatch.compile`` span."""
    operands, go, kind = ENTRANCES[entrance]
    dispatch.clear_cache()
    if warm:
        go(*operands())
    args = operands()
    telemetry.clear_spans()
    before = dispatch.cache_stats()
    go(*args)
    after = dispatch.cache_stats()
    (span,) = _launches()
    assert after["dispatches"] - before["dispatches"] == 1
    assert span.attrs["kind"] == kind and span.depth == 0
    assert span.attrs["ops"] == after["fused_ops"] - before["fused_ops"] == OPS[entrance]
    assert span.attrs["donated"] is bool(after["donations"] - before["donations"])
    assert span.attrs["store"] is bool(after["stores"] - before["stores"]) is (kind == "cast_store")
    assert span.attrs["fresh"] is (not warm) and span.attrs["folded"] == 0
    assert set(span.attrs) == set(ATTRS) and all(type(span.attrs[k]) is t for k, t in ATTRS.items()), span.attrs
    compiles = [r for r in telemetry.get_spans() if r.name == "dispatch.compile"]
    assert len(compiles) == (0 if warm else 1)
    for c in compiles:
        assert span.start_ns <= c.start_ns and _end(c) <= _end(span) and c.depth == 1


@pytest.mark.parametrize("entrance", sorted(ENTRANCES), ids=[ENTRANCES[e][2] for e in sorted(ENTRANCES)])
def test_no_entrance_waits_for_the_device(one_device, monkeypatch, entrance):
    """An entrance returns at its enqueue: over 40 warm launches of one key,
    neither ``jax.block_until_ready`` nor the array type's own
    ``block_until_ready`` is called between the entrance and its return, and
    each launch's record is in the ring when it returns."""
    operands, go, kind = ENTRANCES[entrance]
    go(*operands())  # the miss
    array_type = type(jnp.zeros(1))
    waits = []
    for _ in range(40):
        args = operands()
        telemetry.clear_spans()
        with monkeypatch.context() as m:
            m.setattr(jax, "block_until_ready", lambda *a, **k: waits.append("jax.block_until_ready"))
            m.setattr(array_type, "block_until_ready", lambda self: waits.append("Array.block_until_ready"))
            go(*args)
        (span,) = _launches()
        assert span.attrs["kind"] == kind and not span.attrs["fresh"]
    assert waits == []


# ------------------------------------------------------------------- a store that waited
@pytest.mark.parametrize("reader", ["outside", "inside"])
def test_a_deferred_store_is_one_span_where_it_is_read(one_device, reader):
    """``x -= m; x /= s`` launches nothing; the read runs both as ONE
    donating store, at depth 0 for a caller outside every span and inside the
    reader's span otherwise."""
    x = _table()
    x.larray_padded
    telemetry.clear_spans()
    before = dispatch.cache_stats()
    x -= 0.5
    x /= 3.0
    assert _launches() == [] and dispatch.cache_stats()["deferred_stores"] - before["deferred_stores"] == 2
    if reader == "inside":
        with telemetry.span("reader") as root:
            x.larray_padded
    else:
        x.larray_padded
    (store,) = _launches()
    assert store.attrs == {"kind": "cast_store", "ops": 5, "fresh": store.attrs["fresh"], "store": True,
                           "donated": True, "folded": 2}  # two operations, two marks, the cast
    if reader == "inside":
        assert store.depth == 1 and root.record.start_ns <= store.start_ns and _end(store) <= _end(root.record)
    else:
        assert store.depth == 0
    after = dispatch.cache_stats()
    assert (after["stores"] - before["stores"], after["donations"] - before["donations"]) == (1, 1)


def test_a_store_whose_buffer_is_shared_does_not_donate(one_device):
    x = _table()
    held = x.larray_padded  # a user's reference: the buffer may not be taken
    want = np.asarray(held) - 0.5
    x -= 0.5
    telemetry.clear_spans()
    x.larray_padded
    (store,) = _launches()
    assert store.attrs["store"] and store.attrs["folded"] == 1 and store.attrs["donated"] is False
    np.testing.assert_array_equal(np.asarray(held) - 0.5, want)  # the holder still reads its values
    np.testing.assert_array_equal(x.numpy(), want[:64])


# ------------------------------------------------------------------- launches that do not come to their enqueue
@pytest.mark.parametrize("kind,marked", [("transient", "fallback"), ("permanent", "error")])
def test_a_launch_that_fails_closes_its_span(one_device, kind, marked):
    """At the ``dispatch.compile`` fault point: a transient fault falls back
    to one eager run inside the same span, marked; a permanent one raises,
    and the span stands in the ring with the exception's name, its ``kind``
    and ``ops``, under the span it ran in."""
    a = _table()
    a.larray_padded
    dispatch.clear_cache()
    telemetry.clear_spans()
    with telemetry.span("caller"), rz.fault_plan({"dispatch.compile": [{"at": 0, "kind": kind}]}):
        if kind == "permanent":
            with pytest.raises(rz.PermanentFault):
                ((a + 5.0) * 2.0).larray_padded
        else:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                got = ((a + 5.0) * 2.0).numpy()
            np.testing.assert_allclose(got, (a.numpy() + 5.0) * 2.0, rtol=1e-6)
    (span,) = _launches()
    assert span.depth == 1 and span.attrs["kind"] == "expr" and span.attrs["ops"] == 2
    if marked == "fallback":
        assert span.attrs["fallback"] is True and "error" not in span.attrs
    else:
        assert span.attrs["error"] == "PermanentFault" and "fallback" not in span.attrs


def test_tracing_off_records_nothing(one_device):
    recorded = telemetry.REGISTRY.get("spans.recorded")
    x = _table()
    telemetry.set_tracing(False)
    before, stats = recorded.value, dispatch.cache_stats()
    x -= 0.5
    total = float((x * 2.0).sum())
    x.larray_padded
    assert dispatch.cache_stats()["dispatches"] - stats["dispatches"] == 2
    assert telemetry.get_spans() == [] and recorded.value == before
    telemetry.set_tracing(True)
    assert total == pytest.approx(float(((_table() - 0.5) * 2.0).sum()), rel=1e-5)


# ------------------------------------------------------------------- the cells
def test_the_scalers_solve_by_span(one_device):
    """The driver's solve, as the chip runs it (no address probe, which reads
    the table after every call): 11 programs through the dispatch layer and
    the selection's one (14 + 1 until PR 36 made a fit one program; ``mean_``
    leaves that program finished, where ``sum / n`` waited for the caller),
    6 of them outside every ``ht.*`` span (five small chains of fitted
    attributes and the last store), 2 stores that donated with the 9 deferred
    ones folded into them."""
    drv = load_py("drivers", "scalers_inplace")
    state = drv.build(load_json("configs", "scalers-inplace.json"), 7, 65536)
    state["table_bytes"] = 1
    for _ in range(2):
        drv.solve(state)
    telemetry.clear_spans()
    before = dispatch.cache_stats()
    drv.solve(state)
    after = dispatch.cache_stats()
    spans = telemetry.get_spans()
    launches, roots = _launches(), [r for r in spans if r.name.startswith("ht.preprocessing.")]
    quantiles = [r for r in spans if r.name == "statistics.quantiles"]
    assert len(launches) == after["dispatches"] - before["dispatches"] == 11
    assert sum(q.attrs["launches"] for q in quantiles) == after["external_dispatches"] - before["external_dispatches"] == 1
    outside = [r for r in launches if r.depth == 0]
    assert len(outside) == 6 and sorted(r.attrs["kind"] for r in outside) == ["cast_store"] + ["expr"] * 5
    for r in launches:
        held = [o for o in roots if o.start_ns <= r.start_ns and _end(r) <= _end(o)]
        assert len(held) == (0 if r.depth == 0 else 1) and not r.attrs["fresh"]
    assert sum(o.attrs["launches"] for o in roots) == 6  # what `scalers_launches` reads
    by_fit = {o.name.split(".")[2]: [r.attrs["kind"] for r in launches if o.start_ns <= r.start_ns and _end(r) <= _end(o)]
              for o in roots if o.name.endswith(".fit")}
    assert by_fit == {"StandardScaler": ["chain"], "MinMaxScaler": ["chain"], "MaxAbsScaler": ["chain"],
                      "RobustScaler": ["cast_store"], "Normalizer": []}
    stores = [r for r in launches if r.attrs["store"]]
    assert [(r.attrs["donated"], r.attrs["folded"], r.depth) for r in stores] == [(True, 6, 1), (True, 3, 0)]
    assert after["deferred_stores"] - before["deferred_stores"] == 9
    assert all(type(v) in (int, bool, str) for r in launches for v in r.attrs.values())


def _hsvd_solve():
    a = ht.array(np.random.default_rng(7).standard_normal((512, 16)).astype(np.float32), split=0)
    return load_py("drivers", "hsvd_rank").solve, {"A": a, "rank": 4}


def _kmeans_solve():
    x = ht.array(np.random.default_rng(7).standard_normal((512, 16)).astype(np.float32), split=0)
    return load_py("drivers", "kmeans_fit").solve, {"x": x, "clusters": 3, "init": x[:3], "max_iter": 5}


@pytest.mark.parametrize("cell", [_hsvd_solve, _kmeans_solve], ids=["hsvd", "kmeans"])
def test_the_jitted_cells_open_no_launch_span(one_device, cell):
    """hSVD and KMeans enter through one jitted program each and cross neither
    ``_iop`` nor ``dispatch``: their drivers' solves leave no such span, so
    the span costs them nothing."""
    solve, state = cell()
    jax.block_until_ready([v.larray_padded for v in state.values() if hasattr(v, "larray_padded")])
    solve(state)
    telemetry.clear_spans()
    before = dispatch.cache_stats()["dispatches"]
    solve(state)
    assert _launches() == [] and dispatch.cache_stats()["dispatches"] == before
    assert telemetry.get_spans()  # the solve's own spans are there


# ------------------------------------------------------------------- the readers
CALLS = 14
#: the launches of one solve as the CPU counts them: (depth, kind, store, donated, folded)
SOLVE = ([(1, "chain", False, False, 0), (0, "expr", False, False, 0)]
         + [(1, "chain", False, False, 0)] + [(0, "expr", False, False, 0)] * 2 + [(1, "chain", False, False, 0)]
         + [(0, "expr", False, False, 0), (1, "cast_store", True, True, 6), (0, "expr", False, False, 0),
            (1, "chain", False, False, 0), (0, "cast_store", True, True, 3)])


def _ring(solves, warmup=2, launches=True, copied=False):
    """A ring as a run leaves it: ``warmup`` solves, then the window's; a
    root of 1 ms every 10 ms, a launch of 0.1 ms (1 ms in the warm-up) every
    11 ms from the solve's first root on, so that the last ones lie behind
    its last root, where the caller reads."""
    from heat_tpu.telemetry.spans import SpanRecord, _append_record

    telemetry.clear_spans()
    me, t = threading.get_ident(), 0
    for i in range(warmup + solves):
        if launches:
            for j, (depth, kind, store, donated, folded) in enumerate(SOLVE):
                attrs = dict(kind=kind, ops=3, fresh=False, store=store, folded=folded,
                             donated=donated and not (copied and depth == 0))
                _append_record(SpanRecord(LAUNCH, t + 10_200_000 + j * 11_000_000, 1_000_000 if i < warmup else 100_000,
                                          me, depth, attrs))
        for name in range(CALLS):
            t += 10_000_000
            if name == 9:
                telemetry.record_span("statistics.quantiles", t + 100_000, 500_000, route="select", passes=17, launches=1)
            telemetry.record_span(f"ht.preprocessing.S{name}.fit", t, 1_000_000, rows=1, launches=1, stores=0, donations=0)
        t += 20_000_000  # the caller's reads behind the last call


#: (reader, case) -> (what fills the ring, the reading wanted, the notes wanted; None: nothing read, with a note)
READER_CASES = {
    ("scalers_programs", "read"): (lambda: _ring(4), 12.0, {"scalers_programs_outside_spans": 6.0}),
    ("scalers_programs", "ring_wrapped"): (lambda: _ring(3, warmup=0), None, None),
    ("scalers_programs", "no_launch_span"): (lambda: _ring(4, launches=False), None, None),
    ("scalers_programs", "tracing_off"): (telemetry.clear_spans, None, None),
    ("scalers_undonated_stores", "read_none"): (lambda: _ring(4), 0.0, {"scalers_store_launches": 2.0, "scalers_folded_stores": 9.0}),
    ("scalers_undonated_stores", "the_last_store_copied"): (lambda: _ring(4, copied=True), 1.0,
                                                            {"scalers_store_launches": 2.0, "scalers_folded_stores": 9.0}),
    ("scalers_undonated_stores", "no_launch_span"): (lambda: _ring(4, launches=False), None, None),
    ("scalers_dispatch_host_ms", "read"): (lambda: _ring(4), 1.1, {"scalers_dispatch_host_ms_outside_spans": 0.6}),
    ("scalers_dispatch_host_ms", "ring_wrapped"): (lambda: _ring(3, warmup=0), None, None),
    ("scalers_dispatch_host_ms", "no_launch_span"): (lambda: _ring(4, launches=False), None, None),
}


@pytest.mark.parametrize("reader,case", sorted(READER_CASES))
def test_dispatch_layer_metric_readers(one_device, reader, case):
    fill, want, notes = READER_CASES[(reader, case)]
    fill()
    run = {"trace": {"top_ops": [], "busy_s": 1.0}, "solves": 4, "window_s": 2.0, "notes": {}}
    got = load_py("layer_metrics", reader).read(run)
    if want is None:
        assert got is None and list(run["notes"]) == [reader]
    else:
        assert got == pytest.approx(want) and run["notes"] == pytest.approx(notes)


def test_the_benchmark_lists_the_three_readers():
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in {r for r, _ in READER_CASES}:
        m = per_layer[name]
        assert (m["layer"], m["moves"], m["source"], m["workloads"]) == ("dispatch", "solve_ms", "program_span", ["scalers-inplace.loop1"])
