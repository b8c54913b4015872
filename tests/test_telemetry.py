"""Telemetry-layer tests (ISSUE 4 tentpole).

The contract under test (docs/observability.md):

* the metrics registry holds process-global counters/gauges/bounded
  histograms with one snapshot/reset/dump_json/expose surface, and the
  four legacy counter islands (dispatch, resilience, overlap, comm) are
  thin byte-compatible views over it — one ``telemetry.snapshot()``
  document covers every domain, legacy reset functions delegate to
  ``reset_all``;
* histograms estimate p50/p90/p99 without storing samples (geometric
  buckets, ~12% relative error) with exact count/sum/min/max;
* spans nest per-thread into a bounded ring buffer, export as Chrome
  trace-event JSON, and are ~free when disabled — tracing off means NO
  ring writes and NO registry writes;
* comm collectives account trace-time payload bytes x participants,
  deterministically: a program traced once and re-executed from the jit
  cache accounts exactly once, and an identical fresh trace accounts
  exactly the same bytes;
* ``HEAT_TPU_METRICS_DUMP=<path>`` writes a valid JSON snapshot at
  interpreter exit (checked in a real subprocess).
"""

import collections
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.telemetry import metrics as tm
from heat_tpu.telemetry import spans as tspans

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_on():
    """Every test starts recording with a clean ring; global counters are
    asserted by delta (the registry is process-global and shared with the
    rest of the suite)."""
    prev = telemetry.set_tracing(True)
    telemetry.clear_spans()
    yield
    telemetry.set_tracing(prev)
    telemetry.clear_spans()


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        reg = tm.MetricsRegistry()
        c = reg.counter("t.hits")
        c.inc()
        c.inc(4)
        g = reg.gauge("t.rate")
        g.set(2.5)
        snap = reg.snapshot()
        assert snap["t.hits"] == 5
        assert snap["t.rate"] == 2.5
        reg.reset()
        assert reg.snapshot() == {"t.hits": 0, "t.rate": 0.0}

    def test_get_or_make_is_idempotent_and_typed(self):
        reg = tm.MetricsRegistry()
        assert reg.counter("t.x") is reg.counter("t.x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("t.x")

    def test_callback_gauge_survives_reset(self):
        reg = tm.MetricsRegistry()
        box = {"v": 7}
        reg.gauge("t.live", fn=lambda: box["v"])
        assert reg.snapshot()["t.live"] == 7
        reg.reset()
        box["v"] = 9
        assert reg.snapshot()["t.live"] == 9  # derived live, never zeroed

    def test_prefix_reset_scopes_to_domain(self):
        reg = tm.MetricsRegistry()
        reg.counter("a.x").inc(3)
        reg.counter("b.y").inc(5)
        reg.reset("a.")
        snap = reg.snapshot()
        assert snap["a.x"] == 0
        assert snap["b.y"] == 5

    def test_histogram_exact_moments_and_quantiles(self):
        h = tm.Histogram("t.h")
        for v in range(1, 101):
            h.observe(float(v))
        assert h.count == 100
        assert h.sum == pytest.approx(5050.0)
        assert h.min == 1.0
        assert h.max == 100.0
        # geometric buckets are ~12% wide; allow 2 buckets of slack
        assert h.quantile(0.5) == pytest.approx(50.0, rel=0.25)
        assert h.quantile(0.9) == pytest.approx(90.0, rel=0.25)
        assert h.quantile(0.99) == pytest.approx(99.0, rel=0.25)
        assert h.quantile(0.0) == 1.0
        assert h.quantile(1.0) <= 100.0
        snap = h.snapshot()
        assert set(snap) == {"count", "sum", "min", "max", "p50", "p90", "p99"}
        h.reset()
        assert h.count == 0 and h.quantile(0.5) is None

    def test_histogram_nonpositive_and_empty(self):
        h = tm.Histogram("t.h2")
        assert h.quantile(0.5) is None and h.min is None
        h.observe(0.0)
        h.observe(-1.0)
        assert h.count == 2
        assert h.quantile(0.5) == -1.0  # clamped to observed min
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_snapshot_include_zero_false_compacts(self):
        reg = tm.MetricsRegistry()
        reg.counter("t.z")
        reg.counter("t.nz").inc()
        reg.histogram("t.he")
        snap = reg.snapshot(include_zero=False)
        assert "t.z" not in snap and "t.he" not in snap
        assert snap["t.nz"] == 1

    def test_dump_json_atomic(self, tmp_path):
        reg = tm.MetricsRegistry()
        reg.counter("t.c").inc(2)
        path = tmp_path / "m.json"
        reg.dump_json(str(path))
        doc = json.loads(path.read_text())
        assert doc["metrics"]["t.c"] == 2
        assert "timestamp" in doc and doc["pid"] == os.getpid()
        assert not list(tmp_path.glob("*.tmp.*"))

    def test_expose_prometheus_text(self):
        reg = tm.MetricsRegistry()
        reg.counter("comm.bytes.psum").inc(64)
        reg.gauge("fit.iter_rate").set(3.5)
        h = reg.histogram("dispatch.compile_ms")
        h.observe(12.0)
        text = reg.expose()
        assert "# TYPE heat_tpu_comm_bytes_psum counter" in text
        assert "heat_tpu_comm_bytes_psum 64" in text
        assert "# TYPE heat_tpu_fit_iter_rate gauge" in text
        assert "# TYPE heat_tpu_dispatch_compile_ms summary" in text
        assert 'heat_tpu_dispatch_compile_ms{quantile="0.5"}' in text
        assert "heat_tpu_dispatch_compile_ms_count 1" in text

    def test_thread_safety_of_counter(self):
        c = tm.Counter("t.mt")

        def work():
            for _ in range(10_000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 40_000


# ----------------------------------------------------------------------
# legacy islands as views + unified reset
# ----------------------------------------------------------------------
class TestLegacyViews:
    def test_snapshot_covers_every_domain(self):
        names = set(telemetry.snapshot())
        for key in (
            "dispatch.hits", "dispatch.compile_ms", "dispatch.cache_size",
            "fault.faults_injected", "retry.retries",
            "overlap.async_saves", "overlap.grad_buckets",
            "spans.recorded",
        ):
            assert key in names, key

    def test_dispatch_view_byte_compatible(self):
        from heat_tpu.core import dispatch

        s = dispatch.cache_stats()
        assert set(s) == {
            "hits", "misses", "dispatches", "fused_ops", "donations",
            "external_dispatches", "compile_fallbacks", "stores", "deferred_stores", "hit_rate",
            "cache_size",
        }
        before = s["external_dispatches"]
        dispatch.record_external_dispatch(5)
        assert dispatch.cache_stats()["external_dispatches"] == before + 5
        assert telemetry.snapshot()["dispatch.external_dispatches"] == before + 5
        dispatch.reset_stats()  # delegates to reset_all("dispatch")
        assert dispatch.cache_stats()["external_dispatches"] == 0

    def test_resilience_view_byte_compatible(self):
        from heat_tpu import resilience as rz

        s = rz.resilience_stats()
        assert set(s) == {
            "sites_evaluated", "faults_injected", "calls", "retries",
            "gave_up", "succeeded_after_retry", "faults_survived",
        }
        with rz.fault_plan({"t.site": [0]}):
            with pytest.raises(rz.TransientFault):
                rz.inject("t.site")
        assert rz.resilience_stats()["faults_injected"] >= 1
        assert telemetry.snapshot()["fault.faults_injected"] >= 1
        rz.reset_fault_stats()
        rz.reset_retry_stats()
        assert rz.resilience_stats() == dict.fromkeys(s, 0)

    def test_overlap_view_byte_compatible(self):
        from heat_tpu.utils import overlap as ov

        s = ov.overlap_stats()
        assert set(s) == {
            "async_saves", "sync_saves", "ckpt_stall_ms", "prefetch_hits",
            "prefetch_misses", "grad_buckets", "prefetch_hit_rate",
        }
        assert isinstance(s["ckpt_stall_ms"], float)
        ov._bump("prefetch_hits", 3)
        ov._bump("prefetch_misses", 1)
        s = ov.overlap_stats()
        assert s["prefetch_hit_rate"] == pytest.approx(
            s["prefetch_hits"] / (s["prefetch_hits"] + s["prefetch_misses"])
        )
        ov.reset_overlap_stats()
        assert ov.overlap_stats()["prefetch_hits"] == 0

    def test_reset_all_domains(self):
        tm.counter("fault.faults_injected").inc()
        tm.counter("comm.calls.psum").inc()
        telemetry.reset_all("faults")
        snap = telemetry.snapshot()
        assert snap["fault.faults_injected"] == 0
        assert snap["comm.calls.psum"] >= 1  # other domains untouched
        telemetry.reset_all()  # everything, including the span ring
        assert telemetry.get_spans() == []
        assert telemetry.snapshot()["comm.calls.psum"] == 0

    def test_reset_all_unknown_domain(self):
        with pytest.raises(ValueError, match="unknown telemetry domain"):
            telemetry.reset_all("nope")


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
class TestSpans:
    def test_nesting_depth_and_attrs(self):
        with telemetry.span("outer", step=1):
            with telemetry.span("inner"):
                pass
        recs = {r.name: r for r in telemetry.get_spans()}
        assert recs["outer"].depth == 0
        assert recs["inner"].depth == 1
        assert recs["outer"].attrs == {"step": 1}
        assert recs["outer"].duration_ns >= recs["inner"].duration_ns
        # inner completed (and was recorded) before outer
        assert telemetry.get_spans()[0].name == "inner"

    def test_decorator_form(self):
        @telemetry.span("decorated", tag="x")
        def fn(a):
            return a * 2

        assert fn(21) == 42
        rec = telemetry.get_spans()[-1]
        assert rec.name == "decorated" and rec.attrs == {"tag": "x"}

    def test_span_records_on_exception(self):
        with pytest.raises(RuntimeError):
            with telemetry.span("boom"):
                raise RuntimeError("x")
        assert telemetry.get_spans()[-1].name == "boom"
        # nesting depth is restored after the raise
        with telemetry.span("after"):
            pass
        assert telemetry.get_spans()[-1].depth == 0

    def test_ring_buffer_bounds(self, monkeypatch):
        monkeypatch.setattr(tspans, "_RING", collections.deque(maxlen=4))
        for i in range(10):
            with telemetry.span(f"s{i}"):
                pass
        names = [r.name for r in telemetry.get_spans()]
        assert names == ["s6", "s7", "s8", "s9"]  # newest win

    def test_disabled_mode_writes_nothing(self):
        telemetry.set_tracing(False)
        recorded_before = telemetry.snapshot()["spans.recorded"]
        snap_before = telemetry.snapshot()
        with telemetry.span("ghost", big=1):
            pass
        assert telemetry.get_spans() == []
        snap_after = telemetry.snapshot()
        assert snap_after["spans.recorded"] == recorded_before
        # no registry writes at all from the disabled protocol
        assert {k: v for k, v in snap_after.items() if k.startswith("spans.")} == {
            k: v for k, v in snap_before.items() if k.startswith("spans.")
        }

    def test_runtime_toggle_returns_previous(self):
        assert telemetry.set_tracing(False) is True
        assert telemetry.set_tracing(True) is False
        assert telemetry.tracing_enabled()

    def test_chrome_trace_schema(self, tmp_path):
        with telemetry.span("parent", step=3):
            with telemetry.span("child", arr=np.int64(2)):
                pass
        path = tmp_path / "trace.json"
        n = telemetry.export_chrome_trace(str(path))
        assert n == 2
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        by_name = {e["name"]: e for e in events}
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert e["pid"] == os.getpid()
            assert isinstance(e["tid"], int)
        # events sorted by ts; child nested inside parent
        assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
        p, c = by_name["parent"], by_name["child"]
        assert p["ts"] <= c["ts"]
        assert p["ts"] + p["dur"] >= c["ts"] + c["dur"]
        assert p["args"] == {"step": 3}
        assert c["args"] == {"arr": "2"}  # non-JSON attrs stringified

    def test_export_clear_flag(self, tmp_path):
        with telemetry.span("one"):
            pass
        telemetry.export_chrome_trace(str(tmp_path / "t.json"), clear=True)
        assert telemetry.get_spans() == []


# ----------------------------------------------------------------------
# comm accounting
# ----------------------------------------------------------------------
class TestCommAccounting:
    def test_psum_bytes_under_shard_map(self):
        comm = ht.WORLD
        n = comm.size
        telemetry.reset_all("comm")
        x = jnp.arange(4 * n, dtype=jnp.float32)

        def make():
            return jax.jit(
                shard_map(
                    lambda v: comm.psum(v),
                    mesh=comm.mesh,
                    in_specs=P(comm.axis_name),
                    out_specs=P(),
                )
            )

        f = make()
        # shard j holds x[4j:4j+4]; the psum of element k over shards is
        # sum_j(4j + k)
        expected_out = np.asarray(x).reshape(n, 4).sum(axis=0)
        np.testing.assert_allclose(np.asarray(f(x)), expected_out)
        snap = telemetry.snapshot()
        assert snap["comm.calls.psum"] == 1
        expected = 4 * 4 * n  # 4-element f32 shard x participants
        assert snap["comm.bytes.psum"] == expected
        # re-executing the compiled program does not re-account
        f(x)
        assert telemetry.snapshot()["comm.calls.psum"] == 1
        # an identical fresh trace accounts exactly the same bytes:
        # trace-time counts are deterministic across re-runs
        make()(x)
        snap2 = telemetry.snapshot()
        assert snap2["comm.calls.psum"] == 2
        assert snap2["comm.bytes.psum"] == 2 * expected

    def test_collective_spans_carry_bytes(self):
        comm = ht.WORLD
        telemetry.reset_all("comm")
        telemetry.clear_spans()
        x = jnp.arange(2 * comm.size, dtype=jnp.float32)
        jax.jit(
            shard_map(
                lambda v: comm.all_gather(v),
                mesh=comm.mesh,
                in_specs=P(comm.axis_name),
                out_specs=P(),
                check_vma=False,
            )
        )(x)
        recs = [r for r in telemetry.get_spans() if r.name == "comm.all_gather"]
        assert len(recs) == 1
        assert recs[0].attrs["bytes"] == telemetry.snapshot()["comm.bytes.all_gather"]
        assert recs[0].attrs["participants"] == comm.size

    def test_exscan_accounts_rounds(self):
        comm = ht.WORLD
        telemetry.reset_all("comm")
        x = jnp.ones((comm.size,), jnp.float32)
        out = jax.jit(
            shard_map(
                lambda v: comm.exscan(v),
                mesh=comm.mesh,
                in_specs=P(comm.axis_name),
                out_specs=P(comm.axis_name),
            )
        )(x)
        np.testing.assert_allclose(np.asarray(out), np.arange(comm.size, dtype=np.float32))
        snap = telemetry.snapshot()
        assert snap["comm.calls.exscan"] == 1
        rounds = max(comm.size - 1, 0).bit_length() + 1
        assert snap["comm.bytes.exscan"] == 4 * comm.size * rounds

    def test_account_implicit(self):
        comm = ht.WORLD
        telemetry.reset_all("comm")
        telemetry.clear_spans()
        with comm.account_implicit("psum", 128, site="test"):
            pass
        snap = telemetry.snapshot()
        assert snap["comm.calls.psum"] == 1
        assert snap["comm.bytes.psum"] == 128 * comm.size
        rec = telemetry.get_spans()[-1]
        assert rec.name == "comm.psum"
        assert rec.attrs["implicit"] is True and rec.attrs["site"] == "test"

    def test_kmeans_fit_records_comm_and_trace(self, tmp_path):
        telemetry.reset_all("comm")
        telemetry.clear_spans()
        ht.random.seed(3)
        x = ht.random.randn(256, 8, split=0).astype(ht.float32)
        ht.cluster.KMeans(n_clusters=4, init="random", max_iter=5, random_state=0).fit(x)
        snap = telemetry.snapshot()
        assert snap["comm.calls.psum"] >= 1
        assert snap["comm.bytes.psum"] > 0
        path = tmp_path / "kmeans_trace.json"
        telemetry.export_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        comm_events = [
            e for e in doc["traceEvents"] if e["name"].startswith("comm.")
        ]
        assert comm_events and all(e["args"]["bytes"] > 0 for e in comm_events)


# ----------------------------------------------------------------------
# instrumentation wiring: dispatch compiles, fit heartbeats
# ----------------------------------------------------------------------
class TestWiring:
    def test_dispatch_compile_histogram_and_span(self):
        from heat_tpu.core import dispatch

        h = telemetry.REGISTRY.get("dispatch.compile_ms")
        telemetry.clear_spans()
        before = h.count
        # a shape no other test uses forces a fresh executable
        a = ht.arange(997, split=0).astype(ht.float32)
        float(((a * 1.7 + 0.3) / 2.0).sum())
        assert h.count >= before + 1
        assert h.quantile(0.5) is not None
        assert any(r.name == "dispatch.compile" for r in telemetry.get_spans())

    def test_fit_heartbeat_gauge_and_span(self):
        from heat_tpu.core.base import resumable_fit_loop

        telemetry.clear_spans()

        def run_chunk(state, n):
            return np.asarray(state) + n, n, 1.0  # never converges by shift

        state, total = resumable_fit_loop(
            run_chunk, lambda: np.zeros(2), max_iter=10, tol=0.0
        )
        assert total == 10
        snap = telemetry.snapshot()
        assert snap["fit.iter_rate"] > 0
        assert snap["fit.shift"] == 1.0
        recs = [r for r in telemetry.get_spans() if r.name == "fit.chunk"]
        assert recs and recs[-1].attrs["iters"] == 10

    def test_checkpoint_spans(self, tmp_path):
        from heat_tpu.utils.checkpoint import Checkpointer

        telemetry.clear_spans()
        ack = Checkpointer(str(tmp_path / "ck")).as_async()
        ack.save(1, {"state": np.arange(8, dtype=np.float32), "n_iter": 1})
        ack.wait()
        ack.restore(1)
        ack.close()
        names = {r.name for r in telemetry.get_spans()}
        assert {
            "checkpoint.save", "checkpoint.async_write", "checkpoint.restore",
            "checkpoint.write", "checkpoint.read",
        } <= names


# ----------------------------------------------------------------------
# atexit dump + summary line + profiling fold-in
# ----------------------------------------------------------------------
class TestSurface:
    def test_atexit_dump_subprocess(self, tmp_path):
        out = tmp_path / "final.json"
        code = (
            "import heat_tpu.telemetry as t\n"
            "t.counter('probe.exit').inc(3)\n"
            "t.histogram('probe.h').observe(2.5)\n"
        )
        env = dict(os.environ)
        env["HEAT_TPU_METRICS_DUMP"] = str(out)
        env["JAX_PLATFORMS"] = "cpu"
        subprocess.run(
            [sys.executable, "-c", code], check=True, env=env, cwd=REPO_ROOT,
            timeout=120,
        )
        doc = json.loads(out.read_text())
        assert doc["metrics"]["probe.exit"] == 3
        assert doc["metrics"]["probe.h"]["count"] == 1

    def test_summary_line(self):
        telemetry.reset_all("comm")
        tm.counter("comm.bytes.psum").inc(2**30)
        line = telemetry.summary_line(iter_rate=12.5)
        assert "comm 1.0000 GiB" in line
        assert "12.5 iter/s" in line
        assert "compile" in line
        assert "n/a" in telemetry.summary_line(iter_rate=0.0)

    def test_monitor_sets_runtime_on_raise(self):
        from heat_tpu.utils import profiling

        @profiling.monitor()
        def boom():
            raise ValueError("x")

        assert boom.last_runtime is None
        with pytest.raises(ValueError):
            boom()
        assert boom.last_runtime is not None and boom.last_runtime >= 0.0

    def test_monitor_measures_success(self):
        from heat_tpu.utils import profiling

        @profiling.monitor("named")
        def ok():
            return jnp.ones(4).sum()

        assert float(ok()) == 4.0
        assert ok.last_runtime > 0.0

    def test_utils_profiling_reexports(self):
        from heat_tpu.utils import profiling as legacy
        from heat_tpu.telemetry import profiling as new

        for name in ("annotate", "monitor", "start_trace", "stop_trace", "trace"):
            assert getattr(legacy, name) is getattr(new, name)

    def test_telemetry_public_surface(self):
        for name in telemetry.__all__:
            assert hasattr(telemetry, name), name
