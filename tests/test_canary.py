"""Canary decision plane: shadow mirroring, online comparison, evented
auto-promote/rollback (ISSUE 15).

The acceptance properties: the comparator grid honors the per-kind
``POLICIES`` contract (bitwise kinds exact, tolerance kinds within
budget, a deliberately-degraded canary detected), shadow traffic never
rides any caller's latency path and compiles nothing in steady state,
a degraded canary under live load is auto-rolled-back with **zero
failed client requests** while the decision lands as a retained event
(exemplar trace_id) on ``/canaryz`` and in a flight-recorder bundle,
and the fleet router rolls per-replica canary state into ``/fleetz``
with divergent-replica highlighting.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import serving
from heat_tpu.core import dispatch
from heat_tpu.fleet import FleetRouter
from heat_tpu.serving import canary as cn
from heat_tpu.serving import model_io
from heat_tpu.telemetry import aggregate
from heat_tpu.telemetry import alerts as talerts
from heat_tpu.telemetry import flight_recorder
from heat_tpu.telemetry import inspect as tinspect
from heat_tpu.telemetry import metrics as tm
from heat_tpu.telemetry import server as tserver

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(7)
PTS = RNG.standard_normal((160, 6)).astype(np.float32)


@pytest.fixture(autouse=True)
def _clean_canary_state():
    cn.reset_canary_state()
    talerts.clear_alerts()
    yield
    cn.reset_canary_state()
    talerts.clear_alerts()


def _fit_kmeans():
    x = ht.array(PTS, split=0)
    return ht.cluster.KMeans(
        n_clusters=3, init="random", max_iter=5, random_state=0
    ).fit(x)


def _degrade_kmeans(est):
    """A deliberately-degraded copy: cluster centers permuted, so every
    predicted label moves — the canary a decision plane must catch."""
    bad = model_io.build_estimator(model_io.export_state(est))
    centers = np.asarray(bad._cluster_centers.numpy())
    bad._cluster_centers = ht.array(centers[::-1].copy(), split=None)
    return bad


@pytest.fixture
def model_dir(tmp_path):
    """v1 = the good model (active), v2 = the SAME model (a worthy
    canary), v3 = the degraded copy (a canary that must fail)."""
    est = _fit_kmeans()
    d = str(tmp_path / "km")
    serving.save_model(est, d, version=1, name="km")
    serving.save_model(est, d, version=2, name="km")
    serving.save_model(_degrade_kmeans(est), d, version=3, name="km")
    return d


@pytest.fixture
def make_service(model_dir):
    made = []

    def make(canary_version=None, fraction=1.0, min_rows=48, **kw):
        svc = serving.InferenceService(max_batch=32, max_delay_ms=1.0, **kw)
        svc.load("km", model_dir, version=1)
        if canary_version is not None:
            svc.load("km", model_dir, version=canary_version, activate=False)
        svc.canary.fraction = fraction
        svc.canary.min_rows = min_rows
        made.append(svc)
        return svc

    yield make
    for svc in made:
        svc.close()


def _drive(svc, n=40, rows=8):
    for i in range(n):
        off = (i * 11) % 64
        svc.predict("km", PTS[off : off + rows])


# ----------------------------------------------------------------------
# the comparator grid
# ----------------------------------------------------------------------
class TestComparator:
    def test_bitwise_exact_pass(self):
        a = np.arange(12, dtype=np.float32).reshape(4, 3)
        out = cn.compare_batch("Lasso", a, a.copy())
        assert out == {"rows": 4, "mismatched": 0, "max_rel_err": 0.0, "mode": "bitwise"}

    def test_bitwise_single_row_mismatch(self):
        a = np.arange(12, dtype=np.float32).reshape(4, 3)
        b = a.copy()
        b[2, 1] += 1e-6  # one ULP-ish wiggle is already a violation
        out = cn.compare_batch("Lasso", a, b)
        assert out["mismatched"] == 1 and out["max_rel_err"] > 0.0

    def test_bitwise_dtype_change_fails_every_row(self):
        a = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = cn.compare_batch("Lasso", a, a.astype(np.float64))
        assert out["mismatched"] == 4

    def test_shape_change_fails_every_row(self):
        a = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = cn.compare_batch("KMeans", a, a[:, :1])
        assert out["mismatched"] == 4

    def test_tolerance_float_within_rtol(self):
        a = RNG.standard_normal((16, 4)).astype(np.float32)
        b = a * (1.0 + 1e-4)  # well inside KMeans rtol=0.02
        out = cn.compare_batch("KMeans", a, b)
        assert out["mode"] == "tolerance"
        assert out["mismatched"] == 0
        assert 0.0 < out["max_rel_err"] < 0.02

    def test_tolerance_float_beyond_rtol(self):
        a = np.ones((8, 2), np.float32)
        b = a.copy()
        b[:3] *= 1.5  # 50% off on 3 rows
        out = cn.compare_batch("KMeans", a, b)
        assert out["mismatched"] == 3

    def test_tolerance_integer_labels_disagreement(self):
        a = np.array([0, 1, 2, 0, 1], np.int32)
        b = np.array([0, 1, 2, 1, 1], np.int32)
        out = cn.compare_batch("KMeans", a, b)
        assert out["rows"] == 5 and out["mismatched"] == 1

    def test_nan_is_never_equal_enough(self):
        a = np.zeros((3, 2), np.float32)
        b = a.copy()
        b[1, 0] = np.nan
        out = cn.compare_batch("Lasso", a, b)
        assert out["mismatched"] == 1


# ----------------------------------------------------------------------
# registry canary-slot tracking
# ----------------------------------------------------------------------
class TestRegistryCanarySlot:
    def test_load_promote_unload_lifecycle(self, model_dir):
        reg = serving.ModelRegistry()
        reg.load("km", model_dir, version=1)
        assert reg.canary_version("km") is None
        reg.load("km", model_dir, version=2, activate=False)
        assert reg.canary_version("km") == 2
        assert reg.models()["km"]["canary"] == 2
        reg.promote("km", 2)
        assert reg.canary_version("km") is None  # the canary went live
        reg.load("km", model_dir, version=3, activate=False)
        assert reg.canary_version("km") == 3
        reg.unload("km", 3)
        assert reg.canary_version("km") is None

    def test_activating_load_clears_the_slot(self, model_dir):
        reg = serving.ModelRegistry()
        reg.load("km", model_dir, version=1)
        reg.load("km", model_dir, version=2, activate=False)
        reg.load("km", model_dir, version=2)  # explicit activation
        assert reg.canary_version("km") is None


# ----------------------------------------------------------------------
# shadow mirroring mechanics
# ----------------------------------------------------------------------
class TestShadowMirroring:
    def test_fraction_systematic_sampling(self, make_service):
        svc = make_service(canary_version=2, fraction=0.5, min_rows=10_000)
        s0 = tm.counter("canary.sampled").value
        o0 = tm.counter("canary.offered").value
        _drive(svc, n=12, rows=4)
        assert svc.canary.wait_idle(30)
        sampled = tm.counter("canary.sampled").value - s0
        offered = tm.counter("canary.offered").value - o0
        # systematic sampling: EXACTLY every second offered batch is
        # mirrored, however the 12 requests coalesced into batches
        assert offered >= 6
        assert sampled == offered // 2

    def test_no_canary_means_no_mirroring(self, make_service):
        svc = make_service(canary_version=None, fraction=1.0)
        s0 = tm.counter("canary.sampled").value
        _drive(svc, n=6)
        assert tm.counter("canary.sampled").value == s0
        assert cn.status("km") is None

    def test_shadowing_compiles_nothing_in_steady_state(self, make_service):
        """The finite-key-set property: the canary rides the SAME
        bucket-padded shapes, so shadow inference is pure cache hits."""
        svc = make_service(canary_version=2, fraction=0.0, min_rows=10_000)
        _drive(svc, n=4, rows=8)  # warm the primary's bucket
        stats0 = dispatch.cache_stats()
        svc.canary.fraction = 1.0
        _drive(svc, n=12, rows=8)
        assert svc.canary.wait_idle(30)
        stats1 = dispatch.cache_stats()
        assert stats1["misses"] == stats0["misses"], "shadowing must not compile"
        st = cn.status("km")
        assert st is not None and st["rows"] > 0


# ----------------------------------------------------------------------
# the decision engine
# ----------------------------------------------------------------------
class TestDecisions:
    def test_healthy_canary_auto_promotes(self, make_service):
        svc = make_service(canary_version=2, min_rows=48)
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["decision"]["action"] == "promoted"
        assert st["decision"]["verdict"] == "pass"
        assert svc.registry.active_version("km") == 2
        assert svc.registry.canary_version("km") is None
        assert not talerts.is_firing("canary:km", labels={"model": "km"})
        # the decision is a retained event with the exemplar trace
        decisions = [e for e in cn.canary_events() if e["kind"] == "decision"]
        assert decisions and decisions[-1]["action"] == "promoted"
        assert decisions[-1]["trace_id"]

    def test_degraded_canary_auto_rolls_back(self, make_service, tmp_path):
        flight_recorder.install(str(tmp_path / "bundles"))
        try:
            svc = make_service(canary_version=3, min_rows=48)
            _drive(svc, n=10, rows=8)
            assert svc.canary.wait_idle(30)
        finally:
            flight_recorder.uninstall()
        st = cn.status("km")
        assert st["decision"]["action"] == "rolled_back"
        assert st["decision"]["verdict"] == "fail"
        assert st["decision"]["reasons"]
        assert svc.registry.active_version("km") == 1  # primary untouched
        assert svc.registry.canary_version("km") is None
        with pytest.raises(KeyError):
            svc.registry.record("km", 3)  # the bad version is gone
        assert talerts.is_firing("canary:km", labels={"model": "km"})
        # the rollback wrote a forensic bundle carrying the canary section
        paths = sorted((tmp_path / "bundles").glob("flight_*.json"))
        assert paths
        doc = tinspect.load_bundle(str(paths[-1]))
        assert doc["reason"] == "canary_rollback:km"
        dec = doc["canary"]["models"]["km"]["decision"]
        assert dec["action"] == "rolled_back" and dec["reasons"]

    def test_observe_only_mode_records_without_acting(self, make_service):
        svc = make_service(canary_version=3, min_rows=48)
        svc.canary.auto = False
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["decision"]["verdict"] == "fail"
        assert st["decision"]["action"] == "observed"
        assert svc.registry.active_version("km") == 1
        assert svc.registry.canary_version("km") == 3  # still resident

    def test_drift_alert_vetoes_then_clears(self, make_service):
        talerts.fire("drift:km", severity="warn", message="synthetic drift",
                     labels={"model": "km"})
        svc = make_service(canary_version=2, min_rows=48)
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["verdict"] == "held" and st["decision"] is None
        assert any("drift" in v for v in st["vetoes"])
        held = [e for e in cn.canary_events()
                if e["kind"] == "decision" and e.get("action") == "held"]
        assert held, "the held verdict must be a retained event"
        # signal clears -> the next compared batch promotes
        talerts.resolve("drift:km", labels={"model": "km"})
        _drive(svc, n=4, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["decision"]["action"] == "promoted"

    def test_slo_alert_vetoes(self, make_service):
        talerts.fire("slo:latency_p99", severity="page", message="burning")
        svc = make_service(canary_version=2, min_rows=48)
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["verdict"] == "held"
        assert any("slo:latency_p99" in v for v in st["vetoes"])

    def test_latency_budget_clause(self, make_service):
        """_evaluate flags a canary whose per-row time blows the budget
        (synthetic window: the clause, isolated from the comparator)."""
        svc = make_service(canary_version=2, min_rows=10_000)
        st = cn._new_state("km", "KMeans", 2, 1, min_rows=10)
        st["rows"] = 20
        st["primary_ms"] = 10.0
        st["canary_ms"] = 10.0 * svc.canary.latency_x * 1.5
        verdict, reasons = svc.canary._evaluate(st)
        assert verdict == "fail" and any("latency" in r for r in reasons)
        st["canary_ms"] = 9.0
        assert svc.canary._evaluate(st) == ("pass", [])

    def test_bitwise_window_allows_zero_mismatches(self, make_service):
        svc = make_service(canary_version=2, min_rows=10_000)
        st = cn._new_state("lasso", "Lasso", 2, 1, min_rows=10)
        st["rows"], st["mismatched"] = 100, 1
        verdict, reasons = svc.canary._evaluate(st)
        assert verdict == "fail" and "bitwise" in reasons[0]

    def test_canary_inference_error_is_terminal(self, make_service):
        """A canary that RAISES is rolled back immediately — no window."""
        svc = make_service(canary_version=2, min_rows=10_000)

        class _Boom:
            def predict(self, x):
                raise RuntimeError("canary kernel exploded")

        # break the canary estimator in place: predict raises
        svc.registry.record("km", 2)["estimator"] = _Boom()
        _drive(svc, n=3, rows=8)
        assert svc.canary.wait_idle(30)
        st = cn.status("km")
        assert st["decision"]["action"] == "rolled_back"
        errors = [e for e in cn.canary_events() if e["kind"] == "error"]
        assert errors and errors[-1]["severity"] == "page"


# ----------------------------------------------------------------------
# surfaces: /healthz fields, /canaryz, /statusz, snapshots, bundles
# ----------------------------------------------------------------------
class TestSurfaces:
    def test_model_healthz_carries_canary_fields(self, make_service):
        svc = make_service(canary_version=2, min_rows=10_000)
        _drive(svc, n=4, rows=8)
        assert svc.canary.wait_idle(30)
        doc = svc.model_health("km")
        assert doc["canary_version"] == 2
        assert doc["shadow_sampled_rows"] > 0
        assert doc["last_canary_verdict"] == "collecting"

    def test_canaryz_routes_html_and_json(self, make_service):
        svc = make_service(canary_version=3, min_rows=48)
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        tserver.stop_server()
        srv = tserver.start_server(0)
        try:
            with urllib.request.urlopen(srv.url + "/canaryz?format=json") as r:
                doc = json.load(r)
            assert doc["models"]["km"]["decision"]["action"] == "rolled_back"
            assert doc["shadow"]["sampled"] > 0
            with urllib.request.urlopen(srv.url + "/canaryz") as r:
                html = r.read().decode()
            assert "km" in html and "rolled_back" in html
            with urllib.request.urlopen(srv.url + "/statusz") as r:
                status = json.load(r)
            assert status["canary"]["models"]["km"]["verdict"] == "fail"
        finally:
            tserver.stop_server()

    def test_canaryz_html_escapes_hostile_names(self, make_service):
        cn.record_event("<script>alert(1)</script>", "decision", "page",
                        "<img src=x onerror=alert(1)>")
        html = cn.render_canaryz_html()
        assert "<script>alert" not in html
        assert "&lt;script&gt;" in html

    def test_tagged_snapshot_and_divergence_merge(self, make_service):
        svc = make_service(canary_version=2, min_rows=10_000)
        _drive(svc, n=4, rows=8)
        assert svc.canary.wait_idle(30)
        snap = aggregate.tag_snapshot()
        assert snap["canary"]["models"]["km"]["canary_version"] == 2
        # two synthetic workers disagreeing on the verdict -> divergent
        s0 = dict(snap, process_index=0)
        s1 = json.loads(json.dumps(snap))
        s1["process_index"] = 1
        s1["canary"]["models"]["km"]["verdict"] = "fail"
        merged = aggregate.merge_snapshots([s0, s1], publish=False)
        entry = merged["canary"]["models"]["km"]
        assert entry["divergent"] is True
        assert set(entry["workers"]) == {"0", "1"}
        # agreeing workers are not divergent
        merged2 = aggregate.merge_snapshots([s0, dict(s0, process_index=1)],
                                            publish=False)
        assert merged2["canary"]["models"]["km"]["divergent"] is False

    def test_inspect_renders_canary_section_in_memory(self, make_service):
        svc = make_service(canary_version=3, min_rows=48)
        _drive(svc, n=10, rows=8)
        assert svc.canary.wait_idle(30)
        text = tinspect.format_bundle(flight_recorder.build_bundle())
        assert "canary decision plane" in text
        assert "rolled_back" in text and "km" in text


# ----------------------------------------------------------------------
# fleet rollup: /fleetz canary table with divergent highlighting
# ----------------------------------------------------------------------
class _FakeCanaryReplica:
    """Minimal replica speaking /readyz + /canaryz for the router's
    health poller."""

    def __init__(self, verdict, version=2):
        self.canary_doc = {
            "timestamp": time.time(),
            "shadow": {},
            "models": {
                "km": {
                    "canary_version": version, "verdict": verdict,
                    "rows": 64, "mismatch_pct": 0.0, "latency_ratio": 1.0,
                    "decision": None, "last_trace_id": "t-1",
                }
            },
            "events": [],
        }
        outer = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _send(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/readyz":
                    self._send(200, {"ready": True, "state": "ready",
                                     "models": ["km"]})
                elif self.path.startswith("/canaryz"):
                    self._send(200, outer.canary_doc)
                else:
                    self._send(404, {"error": "?"})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="fake-canary-replica",
            daemon=True,
        )
        self._thread.start()
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}"

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


class TestFleetRollup:
    def test_fleetz_reports_divergent_replicas(self):
        r1 = _FakeCanaryReplica(verdict="pass")
        r2 = _FakeCanaryReplica(verdict="fail")
        router = FleetRouter(replicas=(r1.url, r2.url), health_period_s=30.0)
        try:
            router.poll_health()
            doc = router.fleetz_report()
            entry = doc["canary"]["km"]
            assert set(entry["replicas"]) == {r1.url, r2.url}
            assert entry["divergent"] is True
            assert sorted(entry["verdicts"]) == ["fail", "pass"]
            html = router.render_fleetz_html()
            assert "divergent" in html and "km" in html
        finally:
            router.close()
            r1.close()
            r2.close()

    def test_fleetz_agreeing_replicas_not_divergent(self):
        r1 = _FakeCanaryReplica(verdict="pass")
        r2 = _FakeCanaryReplica(verdict="pass")
        router = FleetRouter(replicas=(r1.url, r2.url), health_period_s=30.0)
        try:
            router.poll_health()
            assert router.fleetz_report()["canary"]["km"]["divergent"] is False
        finally:
            router.close()
            r1.close()
            r2.close()


# ----------------------------------------------------------------------
# the e2e acceptance scenario + the subprocess crash surface
# ----------------------------------------------------------------------
class TestEndToEnd:
    def test_degraded_canary_rolled_back_under_live_load(
        self, make_service, tmp_path
    ):
        """ISSUE 15 acceptance: a deliberately-degraded canary under
        concurrent live load is auto-rolled-back with ZERO failed client
        requests; the decision is a retained /canaryz event with an
        exemplar trace_id and a flight-recorder bundle records the
        failed comparison."""
        flight_recorder.install(str(tmp_path / "bundles"))
        tserver.stop_server()
        srv = tserver.start_server(0)
        errors = []
        try:
            svc = make_service(canary_version=3, min_rows=96)

            def client(worker):
                sizes = (3, 5, 8, 13)
                for i in range(40):
                    off = (worker * 31 + i * 7) % 64
                    n = sizes[(worker + i) % len(sizes)]
                    try:
                        out = svc.predict("km", PTS[off : off + n], timeout=30)
                        assert out.shape[0] == n
                    except Exception as e:  # pragma: no cover - the assertion target
                        errors.append(e)

            threads = [
                threading.Thread(target=client, args=(w,), daemon=True)
                for w in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert svc.canary.wait_idle(60)
            assert errors == [], f"live clients failed: {errors[:3]}"

            st = cn.status("km")
            assert st["decision"]["action"] == "rolled_back"
            assert svc.registry.active_version("km") == 1
            # the decision is retained on /canaryz with an exemplar trace
            with urllib.request.urlopen(srv.url + "/canaryz?format=json") as r:
                doc = json.load(r)
            decisions = [e for e in doc["events"] if e["kind"] == "decision"]
            assert decisions and decisions[-1]["action"] == "rolled_back"
            assert decisions[-1]["trace_id"], "decision must carry its exemplar"
            # the flight-recorder bundle records the failed comparison
            paths = sorted((tmp_path / "bundles").glob("flight_*.json"))
            assert paths
            bundle = tinspect.load_bundle(str(paths[-1]))
            assert bundle["reason"] == "canary_rollback:km"
            assert bundle["canary"]["models"]["km"]["mismatched_rows"] > 0
        finally:
            tserver.stop_server()
            flight_recorder.uninstall()

    def test_subprocess_rollback_bundle_and_inspect_cli(self, tmp_path):
        """The crash surface, end to end in a REAL process: the
        auto-rollback's bundle lands on disk checksum-valid with the
        canary section, and the inspect CLI renders it."""
        bundles = tmp_path / "bundles"
        child = f"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import heat_tpu as ht
from heat_tpu import serving
from heat_tpu.serving import canary as cn, model_io

rng = np.random.default_rng(7)
pts = rng.standard_normal((160, 6)).astype(np.float32)
x = ht.array(pts, split=0)
km = ht.cluster.KMeans(n_clusters=3, init='random', max_iter=5, random_state=0).fit(x)
bad = model_io.build_estimator(model_io.export_state(km))
c = np.asarray(bad._cluster_centers.numpy())
bad._cluster_centers = ht.array(c[::-1].copy(), split=None)
d = {str(tmp_path / 'km')!r}
serving.save_model(km, d, version=1, name='km')
serving.save_model(bad, d, version=2, name='km')
svc = serving.InferenceService(max_batch=32, max_delay_ms=1.0)
svc.load('km', d, version=1)
svc.load('km', d, version=2, activate=False)
svc.canary.fraction = 1.0
svc.canary.min_rows = 48
for i in range(10):
    svc.predict('km', pts[(i * 11) % 64 : (i * 11) % 64 + 8])
assert svc.canary.wait_idle(60)
st = cn.status('km')
assert st['decision']['action'] == 'rolled_back', st
svc.close()
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["HEAT_TPU_FLIGHT_RECORDER"] = str(bundles)
        proc = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True,
            cwd=REPO_ROOT, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr.decode()[-3000:]
        paths = sorted(bundles.glob("flight_*.json"))
        assert len(paths) == 1
        doc = tinspect.load_bundle(str(paths[0]))  # CRC-verified
        assert doc["reason"] == "canary_rollback:km"
        km_doc = doc["canary"]["models"]["km"]
        assert km_doc["decision"]["action"] == "rolled_back"
        assert km_doc["mismatched_rows"] > 0
        assert any(e["kind"] == "comparison" for e in doc["canary"]["events"])
        # the inspect CLI renders the canary section end to end
        res = subprocess.run(
            [sys.executable, "-m", "heat_tpu.telemetry.inspect", str(paths[0])],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, cwd=REPO_ROOT, timeout=300,
        )
        assert res.returncode == 0, res.stderr.decode()[-2000:]
        out = res.stdout.decode()
        assert "canary decision plane" in out
        assert "rolled_back" in out and "km" in out
