"""Runtime-introspection layer tests (ISSUE 6 tentpole).

The contract under test (docs/observability.md):

* the HTTP endpoint (``telemetry/server.py``) serves ``/metrics``
  ``/varz`` ``/healthz`` ``/trace`` ``/statusz`` on an ephemeral port,
  scrapeable WHILE a real resumable KMeans fit runs in the process;
* ``/healthz`` reports the fit heartbeat + last durable checkpoint step
  and flips to 503 when the heartbeat is older than
  ``HEAT_TPU_HEALTH_MAX_AGE_S``;
* a subprocess crashed by an injected ``PermanentFault`` leaves a
  checksum-valid, schema-complete crash bundle that
  ``python -m heat_tpu.telemetry.inspect`` renders;
* cross-worker snapshot merging is a deterministic pure function and the
  ``telemetry.straggler_score`` gauge fires on synthetic 2-worker skew;
* per-executable cost accounting records XLA flops/bytes per dispatch
  cache key when enabled and stays inert when disabled;
* every knob this layer introduced is registered in the central table
  (H201-clean by construction).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.telemetry import aggregate, flight_recorder
from heat_tpu.telemetry import inspect as tinspect
from heat_tpu.telemetry import metrics as tm
from heat_tpu.telemetry import server as tserver

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_on():
    prev = telemetry.set_tracing(True)
    telemetry.clear_spans()
    yield
    telemetry.set_tracing(prev)
    telemetry.clear_spans()


@pytest.fixture
def live_server():
    srv = tserver.start_server(0)
    yield srv
    tserver.stop_server()


def _get(srv, route):
    with urllib.request.urlopen(f"{srv.url}{route}", timeout=10) as r:
        return r.status, r.read().decode("utf-8")


def _data():
    ht.random.seed(7)
    return ht.random.randn(240, 6, split=0).astype(ht.float32)


# ----------------------------------------------------------------------
# HTTP endpoints against a live resumable fit
# ----------------------------------------------------------------------
class TestEndpoints:
    def test_all_routes_serve_during_live_fit(self, live_server, tmp_path):
        """Every route answers 200 while a resumable KMeans fit is
        actually running in this process (scraper thread polls /healthz
        concurrently with the fit)."""
        codes = []
        stop = threading.Event()

        def scrape():
            while not stop.is_set():
                try:
                    codes.append(_get(live_server, "/healthz")[0])
                except OSError:  # server busy starting; keep polling
                    pass
                time.sleep(0.005)

        t = threading.Thread(target=scrape, daemon=True)
        t.start()
        try:
            km = ht.cluster.KMeans(
                n_clusters=4, init="random", max_iter=20, tol=-1.0,
                random_state=0, checkpoint_every=2, checkpoint_dir=str(tmp_path / "ck"),
            ).fit(_data())
        finally:
            stop.set()
            t.join(timeout=10)
        assert km.cluster_centers_ is not None
        assert codes and all(c == 200 for c in codes)

        status, body = _get(live_server, "/metrics")
        assert status == 200
        assert "heat_tpu_fit_iter_rate" in body
        assert "# TYPE" in body

        status, body = _get(live_server, "/varz")
        doc = json.loads(body)
        assert status == 200
        assert doc["pid"] == os.getpid()
        assert doc["metrics"]["fit.heartbeat_ts"] > 0

        status, body = _get(live_server, "/trace")
        trace = json.loads(body)
        assert status == 200
        assert any(e["name"] == "fit.chunk" for e in trace["traceEvents"])

        status, body = _get(live_server, "/statusz")
        statusz = json.loads(body)
        assert status == 200
        assert "HEAT_TPU_HTTP_PORT" in statusz["knobs"]
        assert statusz["runtime"]["jax"] is not None
        assert statusz["dispatch"] is not None
        assert 0.0 <= statusz["dispatch"]["hit_rate"] <= 1.0
        assert isinstance(statusz["dispatch"]["cache_keys"], list)

        status, doc = _get(live_server, "/healthz")
        health = json.loads(doc)
        assert status == 200
        assert health["status"] == "ok"
        assert health["heartbeat_age_s"] is not None
        assert health["checkpoint"]["last_step"] is not None

    def test_unknown_route_404_and_root_index(self, live_server):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(live_server, "/nope")
        assert exc.value.code == 404
        status, body = _get(live_server, "/")
        assert status == 200 and "/statusz" in body

    def test_root_index_lists_every_server_route(self, live_server):
        status, body = _get(live_server, "/")
        assert status == 200
        for entry in SERVER_ROUTES:
            assert entry["route"] in body.split(), entry["route"]

    def test_metrics_exposition_hygiene(self, live_server):
        """/metrics declares OpenMetrics (the payload carries exemplar
        syntax) and terminates with ``# EOF``."""
        x = ht.random.randn(64, 4, split=0).astype(ht.float32)
        float((x * 2.0 + 1.0).sum())
        with urllib.request.urlopen(f"{live_server.url}/metrics", timeout=10) as r:
            status, ctype, body = r.status, r.headers["Content-Type"], r.read().decode()
        assert status == 200
        assert ctype == "application/openmetrics-text; version=1.0.0; charset=utf-8"
        assert body.rstrip("\n").endswith("# EOF")
        # the dispatch layer's gauges ride in the same payload
        assert "heat_tpu_dispatch_cache_size" in body

    def test_start_is_idempotent_and_stop_clears(self):
        a = tserver.start_server(0)
        b = tserver.start_server(0)
        assert a is b and tserver.server_running()
        tserver.stop_server()
        assert not tserver.server_running()
        tserver.stop_server()  # second stop is a no-op

    def test_healthz_flips_unhealthy_on_stale_heartbeat(self, live_server, monkeypatch):
        tm.gauge("fit.heartbeat_ts").set(time.time() - 60.0)
        monkeypatch.setenv("HEAT_TPU_HEALTH_MAX_AGE_S", "5")
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(live_server, "/healthz")
        assert exc.value.code == 503
        doc = json.loads(exc.value.read().decode("utf-8"))
        assert doc["status"] == "stale"
        # a fresh heartbeat restores health without restarting anything
        tm.gauge("fit.heartbeat_ts").set(time.time())
        status, body = _get(live_server, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"

    def test_healthz_idle_before_any_fit(self, live_server, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_HEALTH_MAX_AGE_S", "5")
        prev = tm.gauge("fit.heartbeat_ts").value
        tm.gauge("fit.heartbeat_ts").set(0.0)
        try:
            status, body = _get(live_server, "/healthz")
            assert status == 200 and json.loads(body)["status"] == "idle"
        finally:
            tm.gauge("fit.heartbeat_ts").set(prev)


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
BUNDLE_KEYS = {
    "schema", "reason", "timestamp", "pid", "exception", "knobs",
    "metrics", "spans", "dispatch", "checkpoint", "runtime",
}


class TestFlightRecorder:
    def test_manual_bundle_schema_and_checksum(self, tmp_path):
        with telemetry.span("probe.crash", step=3):
            pass
        tm.counter("probe.fr").inc(2)
        path = flight_recorder.dump_bundle(
            ValueError("manual probe"), reason="manual", directory=str(tmp_path)
        )
        doc = tinspect.load_bundle(path)  # checksum-verified load
        assert BUNDLE_KEYS <= set(doc)
        assert doc["schema"] == flight_recorder.BUNDLE_SCHEMA
        assert doc["exception"]["type"] == "ValueError"
        assert any(s["name"] == "probe.crash" for s in doc["spans"])
        assert doc["metrics"]["probe.fr"] >= 2
        assert "HEAT_TPU_FLIGHT_RECORDER" in doc["knobs"]
        text = tinspect.format_bundle(doc)
        assert "ValueError: manual probe" in text and "probe.crash" in text

    def test_corrupt_bundle_fails_loudly(self, tmp_path):
        from heat_tpu.resilience.errors import ChecksumError

        path = flight_recorder.dump_bundle(
            RuntimeError("x"), reason="manual", directory=str(tmp_path)
        )
        with open(path, "a") as f:  # deliberate corruption (tests are not linted)
            f.write(" ")
        with pytest.raises(ChecksumError):
            tinspect.load_bundle(path)

    def test_install_uninstall_hooks(self, tmp_path):
        prev_hook = sys.excepthook
        d = flight_recorder.install(str(tmp_path))
        try:
            assert flight_recorder.installed() and d == str(tmp_path)
            assert sys.excepthook is not prev_hook
            flight_recorder.install(str(tmp_path))  # idempotent
        finally:
            flight_recorder.uninstall()
        assert not flight_recorder.installed()
        assert sys.excepthook is prev_hook

    def test_subprocess_crash_leaves_valid_bundle(self, tmp_path):
        """A child killed by an injected PermanentFault mid-fit leaves a
        checksum-valid, schema-complete bundle that the inspect CLI
        renders."""
        bundles = tmp_path / "bundles"
        child = (
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "jax.config.update('jax_enable_x64', True)\n"
            "import heat_tpu as ht\n"
            "ht.random.seed(11)\n"
            "x = ht.random.randn(240, 6, split=0).astype(ht.float32)\n"
            "ht.cluster.KMeans(n_clusters=4, init='random', max_iter=40,\n"
            "                  tol=-1.0, random_state=2, checkpoint_every=2,\n"
            f"                  checkpoint_dir={str(tmp_path / 'ck')!r}).fit(x)\n"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["HEAT_TPU_FLIGHT_RECORDER"] = str(bundles)
        env["HEAT_TPU_FAULT_PLAN"] = json.dumps(
            {"plan": {"kmeans.iter": [{"at": 2, "kind": "permanent"}]}}
        )
        proc = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True,
            cwd=REPO_ROOT, timeout=300,
        )
        assert proc.returncode != 0
        assert b"PermanentFault" in proc.stderr
        paths = sorted(bundles.glob("flight_*.json"))
        assert len(paths) == 1
        doc = tinspect.load_bundle(str(paths[0]))  # CRC-verified
        assert BUNDLE_KEYS <= set(doc)
        assert doc["reason"] == "unhandled_exception"
        assert doc["exception"]["type"] == "PermanentFault"
        assert doc["exception"]["site"] == "kmeans.iter"
        assert any(s["name"] == "fit.chunk" for s in doc["spans"])
        # boundaries: inject#0(total=2)->save(2), inject#1(4)->save(4),
        # inject#2(6) raises before save(6) -> last durable step is 4
        assert doc["checkpoint"]["last_step"] == 4
        assert doc["knobs"]["HEAT_TPU_FAULT_PLAN"]["set"] is True

        # the inspect CLI renders it end to end
        res = subprocess.run(
            [sys.executable, "-m", "heat_tpu.telemetry.inspect", str(paths[0])],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, cwd=REPO_ROOT, timeout=300,
        )
        assert res.returncode == 0, res.stderr.decode()[-2000:]
        out = res.stdout.decode()
        assert "PermanentFault" in out and "fit.chunk" in out
        assert "last durable step: 4" in out

    def test_crashed_process_leaves_bundle_and_metrics_dump(self, tmp_path):
        """A crashed subprocess leaves BOTH a flight-recorder bundle and the
        CRC-verified ``HEAT_TPU_METRICS_DUMP`` atexit JSON, the latter with
        its registered sections (the tenant meter's accounts)."""
        bundles = tmp_path / "bundles"
        dump = tmp_path / "metrics.json"
        child = (
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import heat_tpu as ht\n"
            "from heat_tpu.telemetry import tenants\n"
            "x = ht.random.randn(64, 4, split=0).astype(ht.float32)\n"
            "for _ in range(6):\n"
            "    float((x * 2.0 + 1.0).sum())\n"
            "from heat_tpu.resilience.errors import PermanentFault\n"
            "raise PermanentFault('boom')\n"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["HEAT_TPU_FLIGHT_RECORDER"] = str(bundles)
        env["HEAT_TPU_METRICS_DUMP"] = str(dump)
        proc = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True,
            cwd=REPO_ROOT, timeout=300,
        )
        assert proc.returncode != 0
        assert b"PermanentFault" in proc.stderr

        paths = sorted(bundles.glob("flight_*.json"))
        assert len(paths) == 1
        doc = tinspect.load_bundle(str(paths[0]))  # CRC-verified
        assert BUNDLE_KEYS <= set(doc)
        assert doc["exception"]["type"] == "PermanentFault"
        assert doc["dispatch"]["stats"]["hits"] >= 5

        from heat_tpu.resilience.atomic import verify_checksum

        verify_checksum(str(dump))
        with open(dump) as f:
            dumped = json.load(f)
        assert dumped["metrics"]["dispatch.hits"] >= 5
        assert dumped["tenants"]["total"]["rows"] == 0


# ----------------------------------------------------------------------
# cross-worker aggregation
# ----------------------------------------------------------------------
def _synthetic_worker(ix, chunk_mean_ms, comm_total_ms=5.0):
    return {
        "process_index": ix,
        "process_count": 2,
        "pid": 1000 + ix,
        "timestamp": 1.0,
        "metrics": {"dispatch.hits": 10 * (ix + 1), "fit.iter_rate": 100.0 / (ix + 1)},
        "span_stats": {
            "fit.chunk": {
                "count": 4,
                "total_ms": 4 * chunk_mean_ms,
                "mean_ms": chunk_mean_ms,
                "max_ms": chunk_mean_ms * 1.2,
            },
            "comm.psum": {
                "count": 2,
                "total_ms": comm_total_ms,
                "mean_ms": comm_total_ms / 2,
                "max_ms": comm_total_ms,
            },
        },
    }


class TestAggregate:
    def test_tag_snapshot_identity(self):
        snap = aggregate.tag_snapshot()
        assert snap["process_index"] == 0 and snap["process_count"] >= 1
        assert snap["pid"] == os.getpid()
        assert isinstance(snap["metrics"], dict)

    def test_span_stats_digest(self):
        telemetry.clear_spans()
        for _ in range(3):
            with telemetry.span("agg.probe"):
                pass
        ss = aggregate.span_stats()
        assert ss["agg.probe"]["count"] == 3
        assert ss["agg.probe"]["total_ms"] >= ss["agg.probe"]["max_ms"]

    def test_merge_is_deterministic_and_order_invariant(self):
        a, b = _synthetic_worker(0, 10.0), _synthetic_worker(1, 30.0)
        m1 = aggregate.merge_snapshots([a, b], publish=False)
        m2 = aggregate.merge_snapshots([b, a], publish=False)
        assert json.dumps(m1, sort_keys=True) == json.dumps(m2, sort_keys=True)
        assert m1["merged"]["dispatch.hits"]["sum"] == 30
        assert m1["merged"]["fit.iter_rate"]["per_worker"] == {"0": 100.0, "1": 50.0}

    def test_straggler_gauge_fires_on_synthetic_skew(self):
        snaps = [_synthetic_worker(0, 10.0, 2.0), _synthetic_worker(1, 30.0, 9.0)]
        merged = aggregate.merge_snapshots(snaps)
        # means [10, 30]: median 20 -> (30 - 20) / 20 = 0.5
        assert merged["skew"]["straggler_score"] == pytest.approx(0.5)
        assert merged["skew"]["chunk_spread"] == pytest.approx(1.0)
        assert merged["skew"]["comm_imbalance"] > 0
        assert tm.gauge("telemetry.straggler_score").value == pytest.approx(0.5)
        assert float(tm.gauge("telemetry.straggler_score").value) != 0.0

    def test_balanced_workers_score_zero(self):
        snaps = [_synthetic_worker(0, 10.0), _synthetic_worker(1, 10.0)]
        merged = aggregate.merge_snapshots(snaps, publish=False)
        assert merged["skew"]["straggler_score"] == 0.0
        assert merged["skew"]["chunk_spread"] == 0.0

    def test_dead_worker_scores_capped_inf(self):
        dead = _synthetic_worker(1, 30.0)
        dead["span_stats"].pop("fit.chunk")
        merged = aggregate.merge_snapshots(
            [_synthetic_worker(0, 10.0), dead], publish=False
        )
        assert merged["skew"]["straggler_score"] == pytest.approx(1e9)

    def test_file_transport_roundtrip(self, tmp_path):
        d = str(tmp_path / "snaps")
        path = aggregate.write_worker_snapshot(d)
        assert os.path.exists(path) and os.path.exists(path + ".crc32")
        snaps = aggregate.read_worker_snapshots(d)
        assert len(snaps) == 1 and snaps[0]["pid"] == os.getpid()
        # single-process gather short-circuits to the local snapshot
        gathered = aggregate.gather_snapshots()
        assert len(gathered) == 1 and gathered[0]["process_index"] == 0


# ----------------------------------------------------------------------
# dispatch cost accounting
# ----------------------------------------------------------------------
class TestCostAccounting:
    def test_records_flops_per_cache_key(self):
        from heat_tpu.core import dispatch

        prev = dispatch.set_cost_accounting(True)
        dispatch.clear_cache()
        try:
            x = ht.arange(64, split=0).astype(ht.float32)
            float((x * 2.0 + 1.0).sum())
            cs = dispatch.cost_summary()
            assert cs["enabled"] and cs["executables"] >= 1
            assert cs["flops_total"] > 0
            assert tm.counter("dispatch.flops_total").value > 0
            rec = next(iter(cs["per_key"].values()))
            assert rec["flops"] >= 0 and "bytes_accessed" in rec
            assert len(dispatch.cache_keys()) >= len(cs["per_key"])
        finally:
            dispatch.set_cost_accounting(prev)
            dispatch.clear_cache()

    def test_disabled_records_nothing(self):
        from heat_tpu.core import dispatch

        prev = dispatch.set_cost_accounting(False)
        dispatch.clear_cache()
        try:
            x = ht.arange(32, split=0).astype(ht.float32)
            float((x + 1.0).sum())
            cs = dispatch.cost_summary()
            assert not cs["enabled"]
            assert cs["executables"] == 0 and cs["per_key"] == {}
        finally:
            dispatch.set_cost_accounting(prev)
            dispatch.clear_cache()

    def test_statusz_carries_cost_summary(self):
        doc = tserver.statusz_report()
        cost = doc["dispatch"]["cost"]
        assert set(cost) >= {"enabled", "executables", "flops_total", "bytes_total"}


# ----------------------------------------------------------------------
# knobs + satellites riding along
# ----------------------------------------------------------------------
class TestKnobsAndSatellites:
    def test_new_knobs_registered(self):
        from heat_tpu.core._env import KNOBS, env_flag, env_float, env_int, env_str

        for name in (
            "HEAT_TPU_HTTP_PORT",
            "HEAT_TPU_HEALTH_MAX_AGE_S",
            "HEAT_TPU_FLIGHT_RECORDER",
            "HEAT_TPU_COST_ANALYSIS",
        ):
            assert name in KNOBS, name
        assert env_int("HEAT_TPU_HTTP_PORT") == 0
        assert env_float("HEAT_TPU_HEALTH_MAX_AGE_S") == 0.0
        assert env_str("HEAT_TPU_FLIGHT_RECORDER") == ""
        assert env_flag("HEAT_TPU_COST_ANALYSIS") is False

    def test_metrics_dump_writes_crc_sidecar(self, tmp_path):
        path = str(tmp_path / "dump.json")
        telemetry.dump_json(path)
        assert os.path.exists(path + ".crc32")
        from heat_tpu.resilience.atomic import verify_checksum

        assert verify_checksum(path) is True
        doc = json.loads(open(path).read())
        assert "metrics" in doc

    def test_chrome_trace_export_is_atomic_no_sidecar(self, tmp_path):
        with telemetry.span("trace.probe"):
            pass
        path = str(tmp_path / "trace.json")
        n = telemetry.export_chrome_trace(path)
        assert n >= 1
        doc = json.loads(open(path).read())
        assert any(e["name"] == "trace.probe" for e in doc["traceEvents"])
        assert not os.path.exists(path + ".crc32")  # perfetto-facing artifact

    def test_weight_cache_eviction_counter(self, monkeypatch):
        from heat_tpu.fft import _leading, _weight_cache

        monkeypatch.setattr(_weight_cache, "_WEIGHT_CACHE_BUDGET", 1 << 20)
        _weight_cache.weight_cache_clear()
        before = tm.counter("fft.weight_cache.evictions").value
        try:
            for n in (64, 128, 192, 256, 320):
                _leading._w_cat(n, "float32", False, 1.0)
            assert tm.counter("fft.weight_cache.evictions").value > before
            s = _weight_cache.weight_cache_stats()
            assert s["nbytes"] <= s["budget_nbytes"] or s["entries"] == 1
            assert "evictions" in s
        finally:
            _weight_cache.weight_cache_clear()

    def test_planar_weight_builders_share_byte_cache(self):
        from heat_tpu.fft import _planar, _weight_cache

        _weight_cache.weight_cache_clear()
        try:
            _planar._dft_w(32, False, "float32")
            _planar._twiddle(8, 4, 32, False, "float32")
            s = _weight_cache.weight_cache_stats()
            assert s["entries"] >= 2 and s["nbytes"] > 0
        finally:
            _weight_cache.weight_cache_clear()


# ----------------------------------------------------------------------
# ISSUE 19 satellite: every server-owned route scrapes clean
# ----------------------------------------------------------------------
SERVER_ROUTES = [r for r in tserver.BUILTIN_ROUTES if r["owner"] == "server"]


def _get_full(srv, route):
    with urllib.request.urlopen(f"{srv.url}{route}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


class TestAllRoutesScrape:
    def test_route_registry_covers_every_server_route(self):
        assert {r["route"] for r in SERVER_ROUTES} == {
            "/metrics", "/varz", "/healthz", "/readyz", "/trace", "/tracez",
            "/statusz", "/sloz", "/driftz", "/canaryz", "/tenantz",
            "/decisionz", "/queryz",
        }
        assert len({r["route"] for r in tserver.BUILTIN_ROUTES}) == len(
            tserver.BUILTIN_ROUTES
        )
        for entry in tserver.BUILTIN_ROUTES:
            assert entry["purpose"] and entry["owner"], entry["route"]

    @pytest.mark.parametrize(
        "entry", SERVER_ROUTES, ids=[r["route"] for r in SERVER_ROUTES]
    )
    def test_route_scrapes_clean(self, live_server, entry):
        route = entry["route"]
        status, ctype, body = _get_full(live_server, route)
        assert status == 200, route
        assert body
        if route == "/metrics":
            assert ctype.startswith("application/openmetrics-text")
            assert body.rstrip().endswith("# EOF")
        elif entry["html"]:
            assert "text/html" in ctype
            sep = "&" if "?" in route else "?"
            jstatus, jctype, jbody = _get_full(
                live_server, f"{route}{sep}format=json"
            )
            assert jstatus == 200 and "application/json" in jctype
            json.loads(jbody)
        else:
            assert "application/json" in ctype
            json.loads(body)

    def test_hostile_names_are_escaped(self, live_server):
        from heat_tpu.telemetry import alerts as talerts
        from heat_tpu.telemetry import journal as tjournal

        hostile = "<script>alert(1)</script>"
        tjournal.reset_journal()
        talerts.clear_alerts()
        try:
            ev = tjournal.emit(
                "canary", "rolled_back", model=hostile,
                tenant=f"t-{hostile}", severity="page",
                message=f"bad {hostile} news",
                evidence={"reason": hostile},
            )
            talerts.fire(
                f"canary:{hostile}", severity="page",
                message=f"alert {hostile}", labels={"model": hostile},
            )
            for route in ("/decisionz", f"/decisionz?event_id={ev['event_id']}"):
                status, _ctype, body = _get_full(live_server, route)
                assert status == 200
                assert "<script>" not in body, route
                assert "&lt;script&gt;" in body, route
        finally:
            tjournal.reset_journal()
            talerts.clear_alerts()
