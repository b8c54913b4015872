"""Spans at the layer boundaries of the solve paths the chip benchmark
times (``ht.linalg.hsvd*``, ``KMeans.fit``, ``ht.fft.fftn``), the operator's function that
reads them beside the device plane (``idle_by_span``), and the benchmark's
three per-layer readers.  All on the CPU: counts, names and containment,
never a time.
"""

import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.parallel.comm import Communication
from heat_tpu.telemetry.profiling import OUTSIDE, _exchange_part, attribute_exchanges, attribute_idle, exchange_exposure, idle_by_span

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from chipbench.run import load_py  # noqa: E402

ROWS, COLS = 512, 16


@pytest.fixture()
def one_device():
    """One device, as on one chip: no collective is accounted, so the
    ring holds the solve path's spans and nothing else."""
    ht.use_comm(Communication(jax.devices()[:1]))
    prev = telemetry.set_tracing(True)
    try:
        yield
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
        ht.use_comm(ht.WORLD)


def _data():
    x = np.random.default_rng(7).standard_normal((ROWS, COLS)).astype(np.float32)
    return ht.array(x * np.geomspace(1.0, 1e-2, COLS, dtype=np.float32), split=0)


def _hsvd_rank(a):
    U, S, V, err = ht.linalg.hsvd_rank(a, 4, compute_sv=True)
    return [U.numpy(), S.numpy(), V.numpy(), np.asarray(err)]


def _hsvd_rtol(a):
    U, S, V, err = ht.linalg.hsvd_rtol(a, 1e-1, compute_sv=True)
    return [U.numpy(), S.numpy(), V.numpy(), np.asarray(err)]


def _kmeans(a):
    km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=5, random_state=2).fit(a)
    return [km.cluster_centers_.numpy(), km.labels_.numpy(), np.asarray(km.inertia_)]


def _fftn(a):
    return [ht.fft.fftn(a).numpy()]


def _ifftn(a):
    return [ht.fft.ifftn(a).numpy()]


FFT_ATTRS = {"shape": f"{ROWS}x{COLS}", "split": 0, "dtype": "float32", "route": "dense", "blocks": 1}  # one device: nothing to trade

#: solve -> (root, its attributes, children in the order they open)
SOLVES = {
    "fftn": (_fftn, "ht.fft.fftn", {**FFT_ATTRS, "kind": "fft"}, [("fft.dispatch", {"blocks": 1}), ("fft.wrap", {})]),
    "ifftn": (_ifftn, "ht.fft.ifftn", {**FFT_ATTRS, "kind": "ifft"}, [("fft.dispatch", {"blocks": 1}), ("fft.wrap", {})]),
    "hsvd_rank": (_hsvd_rank, "ht.linalg.hsvd", {"rows": ROWS, "cols": COLS, "split": 0, "rank": 4},
                  [("hsvd.dispatch", {"path": "rank"}), ("hsvd.wrap", {})]),
    "hsvd_rtol": (_hsvd_rtol, "ht.linalg.hsvd", {"rows": ROWS, "cols": COLS, "split": 0, "rtol": 1e-1},
                  [("hsvd.dispatch", {"path": "rtol"}), ("hsvd.wrap", {})]),
    "kmeans": (_kmeans, "ht.cluster.KMeans.fit", {"rows": ROWS, "features": COLS, "clusters": 3, "max_iter": 5},
               [("kmeans.init", {}), ("kmeans.loop", {}), ("kmeans.assign", {})]),
}


def _end(rec):
    return rec.start_ns + rec.duration_ns


@pytest.mark.parametrize("solve", sorted(SOLVES))
def test_solve_leaves_the_tables_spans(one_device, solve):
    fn, root_name, root_attrs, children = SOLVES[solve]
    a = _data()
    fn(a)  # the first call compiles: `dispatch.compile` of the eager helpers lands here
    telemetry.clear_spans()
    fn(a)
    spans = sorted(telemetry.get_spans(), key=lambda r: r.start_ns)
    assert [r.name for r in spans] == [root_name] + [name for name, _ in children]
    root, kids = spans[0], spans[1:]
    assert root.depth == 0 and root.attrs == root_attrs
    assert all(isinstance(v, (int, float, str, type(None))) for v in root.attrs.values())
    for kid, (_, attrs), after in zip(kids, children, [root.start_ns] + [_end(k) for k in kids]):
        assert kid.depth == 1 and kid.attrs == attrs
        assert after <= kid.start_ns and _end(kid) <= _end(root)  # inside the root, one after the other
    assert {r.thread_id for r in spans} == {threading.get_ident()}
    assert {r.trace_id for r in spans} == {None}  # no identifier beside thread, depth and time


@pytest.mark.parametrize("split,route", [(0, "pencil"), (1, "local"), (None, "dense")])
def test_fftn_names_its_route_on_the_mesh(split, route):
    """Over the whole mesh: the split axis among the transformed ones is the
    pencil, beside them the slab's own transform; the trace of the program
    (the first call) leaves the two ``comm.all_to_all`` inside ``fft.dispatch``."""
    prev = telemetry.set_tracing(True)
    try:
        a = ht.array(np.random.default_rng(3).standard_normal((24, 16, 6)).astype(np.float32), split=split)
        telemetry.clear_spans()
        ht.fft.fftn(a, axes=(0, 2))
        first = [r.name for r in sorted(telemetry.get_spans(), key=lambda r: r.start_ns)]
        telemetry.clear_spans()
        ht.fft.fftn(a, axes=(0, 2))
        spans = sorted(telemetry.get_spans(), key=lambda r: r.start_ns)
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
    assert [r.name for r in spans] == ["ht.fft.fftn", "fft.dispatch", "fft.wrap"]
    assert spans[0].attrs == {"shape": "24x16x6", "split": split, "dtype": "float32", "kind": "fft", "route": route, "blocks": 1}
    assert spans[1].attrs == {"blocks": 1}  # a small slab: the pencil in one block (tests/test_fft_pencil_kinds.py cuts one)
    assert first.count("comm.all_to_all") == (2 if route == "pencil" else 0)
    assert [n for n in first if not n.startswith(("comm.", "dispatch."))] == ["ht.fft.fftn", "fft.dispatch", "fft.wrap"]


def test_resumable_fit_initializes_inside_its_loop(one_device, tmp_path):
    a = _data()
    ht.cluster.KMeans(n_clusters=3, init="random", max_iter=4, random_state=2,
                      checkpoint_every=2, checkpoint_dir=str(tmp_path)).fit(a)
    by_name = {r.name: r for r in telemetry.get_spans()}
    loop, init = by_name["kmeans.loop"], by_name["kmeans.init"]
    assert (loop.depth, init.depth, by_name["kmeans.assign"].depth) == (1, 2, 1)
    assert loop.start_ns <= init.start_ns and _end(init) <= _end(loop)


@pytest.mark.parametrize("solve", sorted(SOLVES))
def test_tracing_off_leaves_nothing_and_changes_no_result(one_device, solve):
    fn = SOLVES[solve][0]
    a = _data()
    traced = fn(a)
    telemetry.clear_spans()
    telemetry.set_tracing(False)
    plain = fn(a)
    assert telemetry.get_spans() == []
    for t, p in zip(traced, plain):
        np.testing.assert_array_equal(t, p)


@pytest.mark.parametrize("solve,root,child", [("hsvd_rank", "ht.linalg.hsvd", "hsvd.dispatch"),
                                              ("kmeans", "ht.cluster.KMeans.fit", "kmeans.loop"),
                                              ("fftn", "ht.fft.fftn", "fft.dispatch")])
def test_spans_land_in_the_profilers_host_plane(one_device, tmp_path, solve, root, child):
    """Under the options ``chipbench/run.py`` traces with, the annotation of
    each span is kept, on the calling thread's line, the child in the root."""
    from jax.profiler import ProfileData

    fn = SOLVES[solve][0]
    a = _data()
    fn(a)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn(a)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    lines = [[(e.name, e.start_ns, e.duration_ns) for e in line.events if e.name in (root, child)]
             for line in host.lines]
    (line,) = [evs for evs in lines if evs]  # one thread holds them all
    (r,) = [e for e in line if e[0] == root]
    (c,) = [e for e in line if e[0] == child]
    assert r[1] <= c[1] and c[1] + c[2] <= r[1] + r[2]
    # no device plane on the CPU: the operator's function finds nothing to split
    assert idle_by_span(str(tmp_path)) == {"idle": [], "traced_s": 0.0, "busy_share": 0.0, "longest": None}
    assert exchange_exposure(str(tmp_path)) == {**NO_EXCHANGE, "devices": 0}


NO_EXCHANGE = {"exposed_s": 0.0, "issue_s": 0.0, "in_flight_s": 0.0, "hidden_s": 0.0, "exposed_by_exchange": {},
               "synchronous": 0, "asynchronous": 0}


@pytest.mark.parametrize("reader", [idle_by_span, exchange_exposure], ids=["idle_by_span", "exchange_exposure"])
def test_a_trace_reader_wants_a_trace(tmp_path, reader):
    with pytest.raises(FileNotFoundError):
        reader(str(tmp_path))


# ---------------------------------------------------------------- attribute_idle
# one solve on the host's thread: root 100..300, dispatch 110..150, wrap 200..260
HOST = [("ht.linalg.hsvd", 100.0, 200.0), ("hsvd.dispatch", 110.0, 40.0), ("hsvd.wrap", 200.0, 60.0)]


def _ops(*intervals):
    return [(f"%op.{i}", float(s), float(e - s)) for i, (s, e) in enumerate(intervals)]


IDLE_CASES = {
    # the device's gap 120..140 lies wholly inside the dispatch span
    "inside_a_child": ([_ops((0, 120), (140, 400))], {"hsvd.dispatch": (20, 1)}, 400, 380 / 400,
                       (20, "hsvd.dispatch")),
    # 140..220 straddles dispatch (10), the root's own time (50) and wrap (20)
    "straddles_spans": ([_ops((0, 140), (220, 400))],
                        {"ht.linalg.hsvd": (50, 1), "hsvd.wrap": (20, 1), "hsvd.dispatch": (10, 1)}, 400, 320 / 400,
                        (50, "ht.linalg.hsvd")),
    # 20..60 and 320..380: no span is open, the caller's own code
    "outside_every_span": ([_ops((0, 20), (60, 320), (380, 400))], {OUTSIDE: (100, 2)}, 400, 300 / 400,
                           (60, OUTSIDE)),
    # two devices over one traced span 0..400: the second starts late (0..50 outside) and idles 120..140
    "two_devices": ([_ops((0, 400)), _ops((50, 120), (140, 400))],
                    {OUTSIDE: (50, 1), "hsvd.dispatch": (20, 1)}, 400, (400 + 330) / 800, (50, OUTSIDE)),
    # a `while` holds its body's operations: nested events are one busy interval
    "nested_operations": ([_ops((0, 130), (10, 60), (70, 120), (145, 400))], {"hsvd.dispatch": (15, 1)}, 400,
                          385 / 400, (15, "hsvd.dispatch")),
    "no_device_plane": ([], {}, 0, 0.0, None),
}


@pytest.mark.parametrize("case", sorted(IDLE_CASES))
def test_attribute_idle(case):
    device_ops, want, traced_ns, busy_share, longest = IDLE_CASES[case]
    got = attribute_idle(device_ops, HOST)
    assert {name: (pytest.approx(s * 1e9), gaps) for name, s, gaps in got["idle"]} == want
    assert [s for _, s, _ in got["idle"]] == sorted((s for _, s, _ in got["idle"]), reverse=True)
    assert got["traced_s"] == pytest.approx(traced_ns / 1e9) and got["busy_share"] == pytest.approx(busy_share)
    assert got["longest"] == (longest and (pytest.approx(longest[0] / 1e9), longest[1]))


# ------------------------------------------------------------------- the readers
def _ring(solves, dispatch_ns=400_000, root_ns=1_000_000, warmup=2, names=("ht.linalg.hsvd", "hsvd.dispatch", "hsvd.wrap")):
    """A ring as a run leaves it: ``warmup`` solves, then the window's."""
    root, dispatch, wrap = names
    telemetry.clear_spans()
    for i in range(warmup + solves):
        t = i * 10_000_000
        telemetry.record_span(dispatch, t + 100_000, 5 * dispatch_ns if i < warmup else dispatch_ns)
        telemetry.record_span(wrap, t + 700_000, 200_000)
        telemetry.record_span(root, t, 5 * root_ns if i < warmup else root_ns, rows=1)


TOP_OPS = [["%fusion.2 fusion f32[12582912,15]", 0.9], ["%gram_syrk.1 custom-call:tpu_custom_call f32[128,128]", 0.5]]

#: (reader, case) -> what the ring / the trace holds, and the reading wanted (None: nothing read, with a note)
READER_CASES = {
    ("dispatch_enqueue_ms", "read"): (lambda: _ring(5), 5, TOP_OPS, 0.4),
    ("dispatch_enqueue_ms", "ring_wrapped"): (lambda: _ring(3, warmup=0), 5, TOP_OPS, None),
    ("dispatch_enqueue_ms", "tracing_off"): (telemetry.clear_spans, 5, TOP_OPS, None),
    ("api_host_ms", "read"): (lambda: _ring(5), 5, TOP_OPS, 0.6),
    ("api_host_ms", "ring_wrapped"): (lambda: _ring(3, warmup=0), 5, TOP_OPS, None),
    ("api_host_ms", "tracing_off"): (telemetry.clear_spans, 5, TOP_OPS, None),
    ("gram_syrk_ms", "read"): (telemetry.clear_spans, 5, TOP_OPS, 100.0),
    ("gram_syrk_ms", "kernel_not_taken"): (telemetry.clear_spans, 5, TOP_OPS[:1], None),
    ("gram_syrk_ms", "no_device_plane"): (telemetry.clear_spans, 5, [], None),
}


@pytest.mark.parametrize("reader,case", sorted(READER_CASES))
def test_layer_metric_readers(one_device, reader, case):
    fill, solves, top_ops, want = READER_CASES[(reader, case)]
    fill()
    run = {"trace": {"top_ops": top_ops, "busy_s": 1.0}, "solves": solves, "window_s": 2.0, "notes": {}}
    got = load_py("layer_metrics", reader).read(run)
    if want is None:
        assert got is None and reader in run["notes"]
    else:
        assert got == pytest.approx(want) and run["notes"] == {}


def _fft_ring(solves):
    _ring(solves, root_ns=900_000, names=("ht.fft.fftn", "fft.dispatch", "fft.wrap"))


#: the chip's names (PR 31's traces): one all-to-all for the float32 slab, two for the complex64 one; the
#: reshape that takes the collective's name is a layout copy and is not counted; seconds summed over four chips
FFT_OPS = [["%a.3 custom-call:X64Combine c64[256,1024,1024]", 0.9], ["%all_to_all.36 all-to-all f32[256,1024,1024]", 0.4],
           ["%all_to_all.40 all-to-all f32[1024,256,1024]", 0.4], ["%all_to_all.44 all-to-all f32[1024,256,1024]", 0.4],
           ["%all_to_all.55 reshape f32[1024,256,1024]", 0.1]]
FFT_WORK = {"bytes": 12 * 2 ** 28, "operations": 1, "chips": 4, "ici_bytes": 9 * 2 ** 28}

#: (reader, case) -> (what fills the ring, the trace's operations, the work model, the reading wanted)
FFT_READER_CASES = {
    ("fft_host_ms", "read"): (lambda: _fft_ring(5), FFT_OPS, FFT_WORK, 0.9),
    ("fft_host_ms", "no_such_span"): (lambda: _ring(5), FFT_OPS, FFT_WORK, None),  # a program without the fft spans
    ("fft_host_ms", "tracing_off"): (telemetry.clear_spans, FFT_OPS, FFT_WORK, None),
    ("fft_alltoall_ms", "read"): (telemetry.clear_spans, FFT_OPS, FFT_WORK, 1000 * 1.2 / 4 / 5),
    ("fft_alltoall_ms", "no_collective"): (telemetry.clear_spans, FFT_OPS[:1], FFT_WORK, None),
    ("fft_alltoall_ms", "no_device_plane"): (telemetry.clear_spans, [], FFT_WORK, None),
    ("fft_alltoall_gbps", "read"): (telemetry.clear_spans, FFT_OPS, FFT_WORK, 9 * 2 ** 28 * 5 / 0.3 / 1e9),
    ("fft_alltoall_gbps", "no_collective"): (telemetry.clear_spans, FFT_OPS[:1], FFT_WORK, None),
}


@pytest.mark.parametrize("reader,case", sorted(FFT_READER_CASES))
def test_fft_layer_metric_readers(one_device, reader, case):
    fill, top_ops, work, want = FFT_READER_CASES[(reader, case)]
    fill()
    run = {"trace": {"top_ops": top_ops, "busy_s": 1.0}, "solves": 5, "window_s": 2.0, "work": work, "notes": {}}
    got = load_py("layer_metrics", reader).read(run)
    if want is None:
        assert got is None and reader in run["notes"]
    else:
        assert got == pytest.approx(want) and run["notes"] == {}


# ---------------------------------------------------------------- exchange_exposure
#: an operation's name in a device trace is its HLO line; the TPU's compiler writes an asynchronous exchange as
#: ``async-start`` / ``async-done`` whose instruction's name alone says what is started (PR 32's traces)
EXCHANGE_NAMES = {
    "synchronous": ("%all_to_all.40 = f32[1024,256,1024]{2,1,0:T(8,128)} all-to-all(%fusion.7), channel_id=2", ("all-to-all", "")),
    "async_start_by_name": ("%all-to-all-start.4 = ((f32[256,64,1024]{2,1,0:T(8,128)}), f32[256,64,1024]{2,1,0:T(8,128)}, u32[], u32[]) "
                            "async-start(%slice-done.3), calls=%wrapped", ("all-to-all", "start")),
    "async_done_by_name": ("%all-to-all-done.4 = f32[256,64,1024]{2,1,0:T(8,128)} async-done(%all-to-all-start.4)", ("all-to-all", "done")),
    "first_pair_has_no_number": ("%all-to-all-start = ((f32[8]), f32[8], u32[], u32[]) async-start(%p)", ("all-to-all", "start")),
    "start_by_opcode": ("%x.1 = (f32[8], f32[8]) all-to-all-start(%p), replica_groups={{0,1}}", ("all-to-all", "start")),
    "permute_done": ("%collective-permute-done.9 = f32[8] collective-permute-done(%collective-permute-start.9)", ("collective-permute", "done")),
    "all_reduce": ("%all-reduce.2 = f32[8,17] all-reduce(%fusion.17), to_apply=%add", ("all-reduce", "")),
    "an_asynchronous_slice_is_none": ("%slice-start.204 = ((f32[256,64,1024]), f32[64,64,1024], s32[]) async-start(%p)", None),
    "a_copy_pair_is_none": ("%copy-done.1 = f32[8] copy-done(%copy-start.1)", None),
    "a_fusion_named_after_one_is_none": ("%all_to_all.55 = f32[1024,256,1024] reshape(%all_to_all.44)", None),
    "no_hlo_line": ("jit_body(16068260962732680508)", None),
}


@pytest.mark.parametrize("case", sorted(EXCHANGE_NAMES))
def test_exchange_operations_by_their_trace_names(case):
    hlo, want = EXCHANGE_NAMES[case]
    assert _exchange_part(hlo) == want


def _hlo(name, opcode):
    return f"%{name} = f32[8] {opcode}(%p)"


#: case -> (one list of XLA Ops a device, one of Async XLA Ops, what is wanted of the result)
EXCHANGE_CASES = {
    # PR 31's pencil: three synchronous all-to-alls of 12 between fusions, on each of two devices
    "synchronous": ([[(_hlo("f.1", "fusion"), 0.0, 10.0), (_hlo("all_to_all.36", "all-to-all"), 10.0, 12.0), (_hlo("f.2", "fusion"), 22.0, 10.0),
                      (_hlo("all_to_all.40", "all-to-all"), 32.0, 12.0), (_hlo("all_to_all.44", "all-to-all"), 44.0, 12.0)]] * 2, [[], []],
                    {"exposed_s": 36e-9, "issue_s": 0.0, "in_flight_s": 0.0, "hidden_s": 0.0, "synchronous": 3, "asynchronous": 0,
                     "exposed_by_exchange": {"all-to-all": 36e-9}, "devices": 2}),
    # PR 32's: a pair in flight 10 with a fusion of 7 between start (1) and done (2): 8 hidden; and one all-reduce left synchronous
    "pairs": ([[(_hlo("all-to-all-start.1", "async-start"), 0.0, 1.0), (_hlo("f.1", "fusion"), 1.0, 7.0), (_hlo("all-to-all-done.1", "async-done"), 8.0, 2.0),
                (_hlo("all-reduce.2", "all-reduce"), 10.0, 0.5), (_hlo("slice-start.3", "async-start"), 11.0, 1.0)]],
              [[(_hlo("all-to-all-start.1", "async-start"), 0.0, 10.0), (_hlo("slice-start.3", "async-start"), 11.0, 4.0)]],
              {"exposed_s": 2.5e-9, "issue_s": 1e-9, "in_flight_s": 10e-9, "hidden_s": 8e-9, "synchronous": 1, "asynchronous": 1,
               "exposed_by_exchange": {"all-to-all": 2e-9, "all-reduce": 0.5e-9}, "devices": 1}),
    # four chips' trace keeps the asynchronous line on the first plane only: in flight is that plane's, not a quarter of it
    "async_line_on_one_plane": ([[(_hlo("all-to-all-start.1", "async-start"), 0.0, 1.0), (_hlo("f.1", "fusion"), 1.0, 7.0),
                                  (_hlo("all-to-all-done.1", "async-done"), 8.0, 2.0)]] * 4,
                                [[(_hlo("all-to-all-start.1", "async-start"), 0.0, 10.0)], [], [], []],
                                {"exposed_s": 2e-9, "issue_s": 1e-9, "in_flight_s": 10e-9, "hidden_s": 8e-9, "synchronous": 0, "asynchronous": 1,
                                 "exposed_by_exchange": {"all-to-all": 2e-9}, "devices": 4}),
    # an exchange inside a ``while``: the loop's own time is not the exchange's, the exchange's is not the loop's
    "nested": ([[(_hlo("while.1", "while"), 0.0, 20.0), (_hlo("all-reduce.2", "all-reduce"), 2.0, 3.0), (_hlo("f.1", "fusion"), 5.0, 10.0)]], [[]],
               {"exposed_s": 3e-9, "issue_s": 0.0, "in_flight_s": 0.0, "hidden_s": 0.0, "synchronous": 1, "asynchronous": 0,
                "exposed_by_exchange": {"all-reduce": 3e-9}, "devices": 1}),
    "no_exchange": ([[(_hlo("f.1", "fusion"), 0.0, 10.0)]], [[]], {**NO_EXCHANGE, "devices": 1}),
    "no_device": ([], [], {**NO_EXCHANGE, "devices": 0}),
}


@pytest.mark.parametrize("case", sorted(EXCHANGE_CASES))
def test_attribute_exchanges(case):
    device_ops, async_ops, want = EXCHANGE_CASES[case]
    got = attribute_exchanges(device_ops, async_ops)
    assert got.pop("exposed_by_exchange") == pytest.approx(want.pop("exposed_by_exchange"))
    assert got == pytest.approx(want)
