"""Spans and counters of ``KMedians.fit`` (PR 37), the pattern of the KMeans
fit's (``tests/test_solve_path_spans.py``): the root with the static plan's
``passes``, the three phases one after the other inside it, one external
dispatch a launch, nothing read back inside ``fit``, and the benchmark's three
readers of them.  All on the CPU: counts, names and containment, never a time.
"""

import os
import sys
import threading

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.cluster import kmedians
from heat_tpu.core import dispatch, statistics
from heat_tpu.parallel.comm import Communication

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402

ROWS, COLS = 512, 3
ROOT = "ht.cluster.KMedians.fit"


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


def _data():
    rng = np.random.default_rng(7)
    return ht.array((rng.standard_normal((ROWS, COLS)) + 3.0 * rng.integers(0, 3, (ROWS, 1))).astype(np.float32), split=0)


def _fit(a, clusters=3, **kw):
    return ht.cluster.KMedians(n_clusters=clusters, init="random", max_iter=5, random_state=2, **kw).fit(a)


def _end(rec):
    return rec.start_ns + rec.duration_ns


@pytest.mark.parametrize("clusters", [1, 3, 4, 5])
def test_fit_leaves_its_spans(one_device, clusters, bits=2, passes=18):
    """The root carries the plan: reads of the points an iteration, the
    assignment's and the selection's (16 counting passes of 2 bits, whatever
    the number of clusters: the kernel's cost hardly grows with them; and the
    neighbours').  The loop's span says how many of them are calls of a
    kernel: the selection's, all 17 (PR 38; the counting passes' 16 before)."""
    a = _data()
    _fit(a, clusters)  # the first call compiles: `dispatch.compile` of the eager helpers lands here
    telemetry.clear_spans()
    _fit(a, clusters)
    spans = sorted(telemetry.get_spans(), key=lambda r: r.start_ns)
    assert [r.name for r in spans] == [ROOT, "kmedians.init", "kmedians.loop", "kmedians.assign"]
    root, kids = spans[0], spans[1:]
    assert root.depth == 0 and root.attrs == {"rows": ROWS, "features": COLS, "clusters": clusters, "max_iter": 5, "passes": passes}
    assert passes == kmedians.passes_an_iteration(np.float32, clusters) == 2 + -(-32 // bits)
    assert [k.attrs for k in kids] == [{}, {"bits": bits, "passes": passes, "kernel_passes": passes - 1}, {}]
    assert bits == statistics._GROUP_BITS
    for kid, after in zip(kids, [root.start_ns] + [_end(k) for k in kids]):
        assert kid.depth == 1 and after <= kid.start_ns and _end(kid) <= _end(root)
    assert {r.thread_id for r in spans} == {threading.get_ident()}


def test_one_external_dispatch_a_launch_and_nothing_read_back(one_device):
    a = _data()
    _fit(a)
    before = dispatch.cache_stats()["external_dispatches"]
    with jax.transfer_guard_device_to_host("disallow"):
        km = _fit(a)
    assert dispatch.cache_stats()["external_dispatches"] - before == 2  # the loop and the final pass
    # the iteration count and the inertia stay on the device until a caller looks
    assert isinstance(km._n_iter, jax.Array) and isinstance(km._inertia, jax.Array)
    assert km.n_iter_ == 5 or km.n_iter_ < 5
    assert isinstance(km._n_iter, int) and isinstance(km.inertia_, float)


def test_a_resumable_fit_initializes_inside_its_loop(one_device, tmp_path):
    a = _data()
    _fit(a, checkpoint_every=2, checkpoint_dir=str(tmp_path))
    by_name = {r.name: r for r in telemetry.get_spans()}
    loop, init = by_name["kmedians.loop"], by_name["kmedians.init"]
    assert (loop.depth, init.depth, by_name["kmedians.assign"].depth) == (1, 2, 1)
    assert loop.start_ns <= init.start_ns and _end(init) <= _end(loop)
    assert by_name[ROOT].attrs["passes"] == loop.attrs["passes"] == 18 and loop.attrs["kernel_passes"] == 17


def test_tracing_off_leaves_nothing_and_changes_no_result(one_device):
    a = _data()
    traced = _fit(a)
    telemetry.clear_spans()
    telemetry.set_tracing(False)
    plain = _fit(a)
    assert telemetry.get_spans() == []
    assert np.array_equal(traced.cluster_centers_.numpy(), plain.cluster_centers_.numpy())
    assert np.array_equal(traced.labels_.numpy(), plain.labels_.numpy())


def test_the_scopes_name_the_passes_in_the_compiled_text(one_device):
    xp = _data().larray_padded
    text = kmedians._programs(None, ROWS, 2, -1.0)[0].lower(xp, xp[:3]).compile().as_text()
    for scope in ("kmedians.assign", "kmedians.select", "kmedians.select/quantile.count"):
        assert f"/{scope}/" in text, scope
    assert " sort(" not in text


@pytest.mark.parametrize("metric,want", [("kmedians_passes", 18), ("kmedians_fit_host_ms", None), ("kmedians_loop_enqueue_ms", None)])
def test_the_benchmarks_readers(one_device, metric, want):
    a = _data()
    _fit(a)
    telemetry.clear_spans()
    for _ in range(3):
        _fit(a)
    reader = load_py("layer_metrics", metric)
    run = {"solves": 3, "notes": {}}
    value = reader.read(run)
    assert run["notes"] == {} and (value == want if want is not None else value > 0)
    root = [r for r in telemetry.get_spans() if r.name == ROOT]
    loop = [r for r in telemetry.get_spans() if r.name == "kmedians.loop"]
    if metric == "kmedians_fit_host_ms":
        assert value == pytest.approx(sum(r.duration_ns for r in root) / 3e6)
    if metric == "kmedians_loop_enqueue_ms":
        assert value == pytest.approx(sum(r.duration_ns for r in loop) / 3e6)
    # a program without the spans (the parent's), or a window the ring does not hold: nothing, and the reason
    telemetry.clear_spans()
    run = {"solves": 3, "notes": {}}
    assert reader.read(run) is None and metric in run["notes"]


@pytest.mark.parametrize("second_body", [False, True], ids=["count_alone", "beside_the_neighbours"])
def test_the_kernels_readers(one_device, second_body):
    """``kmedians_count_ms`` and ``kmedians_count_roofline_pct`` read the
    kernel by its name in the reduced device trace; how many passes a solve
    makes comes from the program's spans (16 a turn, 5 turns), their bytes
    from the driver's work model; the share cannot pass 100 while a pass
    takes the memory's time or more.  The kernel's second body
    (``%kmedians_neighbours.N``, PR 38) is another operation by name: with it
    among the largest the two read what they read without it.  No such
    operation (the CPU, the program before PR 37): nothing, and the reason."""
    a = _data()
    _fit(a)
    telemetry.clear_spans()
    for _ in range(3):
        _fit(a)
    work = load_py("drivers", "kmedians_fit").work({"rows": ROWS, "features": COLS, "clusters": 3, "max_iter": 5})
    pass_s = work["count_pass_bytes"] / 819e9
    trace = {"top_ops": [["%kmedians_count.8 custom-call:tpu_custom_call s32[3,4,3,8,512]", 3 * 80 * pass_s * 1.25],
                         ["%fusion.37 fusion s32[1,512]", 0.1]]}
    if second_body:
        trace["top_ops"].insert(1, ["%kmedians_neighbours.9 custom-call:tpu_custom_call s32[3,3,8,512]", 3 * 5 * pass_s * 1.3])
    run = {"solves": 3, "notes": {}, "trace": trace, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}, "work": work}
    assert load_py("layer_metrics", "kmedians_count_ms").read(run) == pytest.approx(1000 * 80 * pass_s * 1.25)
    assert load_py("layer_metrics", "kmedians_count_roofline_pct").read(run) == pytest.approx(80.0)
    assert run["notes"] == {"kmedians_count_passes_a_solve": 80.0}
    for metric in ("kmedians_count_ms", "kmedians_count_roofline_pct"):
        run = {"solves": 3, "notes": {}, "trace": {"top_ops": []}, "peaks": run["peaks"], "work": work}
        assert load_py("layer_metrics", metric).read(run) is None and metric in run["notes"]
