"""Fleet-scale serving: router failure modes, AOT cold-start cache,
readiness split, autoscaler hysteresis.

The acceptance properties (ISSUE 13): a replica kill under live load
costs zero client-visible failures (bounded-retry failover absorbs the
loss), the typed 503 fires only when NO replica can take the model,
the per-replica circuit breaker ejects/half-open-probes/readmits, a
draining replica receives no new work while in-flight work finishes,
and a corrupt or fingerprint-stale AOT artifact falls back to a fresh
compile (never a wrong program, never an error).

Router failure modes are driven against in-process *scriptable* fake
replicas (real sockets, deterministic failures); one subprocess test
exercises the real ``python -m heat_tpu.fleet.replica`` lifecycle
(spawn -> prewarm from the AOT cache -> ready -> SIGTERM drain ->
exit 0).
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import serving
from heat_tpu.core import aot_cache, dispatch
from heat_tpu.fleet import FleetAutoscaler, FleetRouter, LocalReplicaSet
from heat_tpu.resilience import NoReplicaError, OverloadedError
from heat_tpu.resilience.atomic import atomic_write, write_checksum
from heat_tpu.serving.admission import AdmissionController
from heat_tpu.telemetry import server as tserver

from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


# ----------------------------------------------------------------------
# scriptable fake replica
# ----------------------------------------------------------------------
class FakeReplica:
    """A real HTTP server speaking the replica protocol, with scripted
    failure modes: ``fail_500`` (predicts answer 500), ``die_mid_request``
    (accept the request, then kill the connection — the mid-request
    crash), ``delay`` (slow predicts), plus live readiness state."""

    def __init__(self, models=("km",), delay=0.0):
        self.models = list(models)
        self.ready = True
        self.state = "ready"
        self.fail_500 = False
        self.die_mid_request = False
        self.delay = float(delay)
        self.served = 0
        self.gets = []
        outer = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _send(self, code, doc, headers=None):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                outer.gets.append(self.path.split("?", 1)[0])
                if self.path == "/readyz":
                    self._send(
                        200 if outer.ready else 503,
                        {"ready": outer.ready, "state": outer.state,
                         "models": outer.models},
                    )
                elif self.path.startswith("/v1/models"):
                    self._send(200, {"models": {m: {} for m in outer.models}})
                else:
                    self._send(404, {"error": "unknown route"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                doc = json.loads(self.rfile.read(n) or b"{}")
                if outer.delay:
                    time.sleep(outer.delay)
                if outer.die_mid_request:
                    # the mid-request kill: request read, no response
                    self.connection.close()
                    return
                if outer.fail_500:
                    self._send(500, {"error": "scripted failure"})
                    return
                if doc.get("model") not in outer.models:
                    self._send(404, {"error": "unknown model"})
                    return
                outer.served += 1
                self._send(200, {
                    "model": doc["model"],
                    "predictions": [0] * len(doc.get("inputs", [0])),
                    "trace_id": doc.get("trace_id"),
                })

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="fake-replica", daemon=True
        )
        self._thread.start()
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}"

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


@pytest.fixture
def replicas():
    made = []

    def make(**kw):
        r = FakeReplica(**kw)
        made.append(r)
        return r

    yield make
    for r in made:
        r.close()


@pytest.fixture
def make_router():
    routers = []

    def make(*urls, **kw):
        kw.setdefault("health_period_s", 30.0)  # tests poll explicitly
        kw.setdefault("cb_cooldown_s", 0.3)
        router = FleetRouter(replicas=urls, **kw)
        routers.append(router)
        router.poll_health()
        return router

    yield make
    for router in routers:
        router.close()


def predict(router, model="km", rows=1, **extra):
    doc = {"model": model, "inputs": [[1.0, 2.0]] * rows}
    doc.update(extra)
    return router.handle("POST", "/v1/predict", json.dumps(doc).encode())


# ----------------------------------------------------------------------
# routing, affinity, failover
# ----------------------------------------------------------------------
class TestRouterRouting:
    def test_predict_routes_and_stamps_trace_id(self, replicas, make_router):
        r = replicas()
        router = make_router(r.url)
        status, out, ctype, _ = predict(router, rows=3)
        assert status == 200
        doc = json.loads(out)
        assert doc["predictions"] == [0, 0, 0]
        assert doc["trace_id"]  # the router stamped one for stitching

    def test_model_affinity_prefers_one_replica(self, replicas, make_router):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url)
        for _ in range(12):
            assert predict(router)[0] == 200
        # rendezvous affinity: an idle fleet serves a model from ONE replica
        assert sorted([a.served, b.served]) == [0, 12]

    def test_kill_mid_request_fails_over_zero_client_failures(
        self, replicas, make_router
    ):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url)
        assert predict(router)[0] == 200
        fav = a if a.served else b
        fav.die_mid_request = True  # accepts the request, kills the socket
        for _ in range(6):
            status, out, _, _ = predict(router)
            assert status == 200, out  # failover absorbed every loss
        # some requests failed over; once the breaker ejects the dying
        # replica the rest route clean without needing one
        assert router.statusz()["failovers"] >= 1

    def test_connect_error_fails_over(self, replicas, make_router):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url)
        assert predict(router)[0] == 200
        fav = a if a.served else b
        other = b if fav is a else a
        fav.close()  # socket gone: connection refused
        before = other.served
        for _ in range(5):
            assert predict(router)[0] == 200
        assert other.served == before + 5

    def test_all_replicas_down_typed_503_with_retry_after(
        self, replicas, make_router
    ):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url, retries=2)
        a.close()
        b.close()
        status, out, _, headers = predict(router)
        assert status == 503
        assert "Retry-After" in headers
        # after a health sweep the verdict is the typed no-replica shed
        router.poll_health()
        status, out, _, headers = predict(router)
        doc = json.loads(out)
        assert status == 503 and doc["cause"] == "no_replica"
        assert float(headers["Retry-After"]) > 0
        assert router.statusz()["no_replica_503"] >= 1

    def test_unknown_model_is_404_not_503(self, replicas, make_router):
        r = replicas(models=("km",))
        router = make_router(r.url)
        status, out, _, _ = predict(router, model="nope")
        assert status == 404
        assert "nope" in json.loads(out)["error"]

    def test_replica_404_learns_and_fails_over(self, replicas, make_router):
        # b hosts the model, a does not; a poll-less router learns from 404s
        a, b = replicas(models=()), replicas(models=("km",))
        router = make_router(a.url, b.url)
        for _ in range(4):
            status, _, _, _ = predict(router)
            assert status == 200
        assert b.served == 4

    def test_global_token_bucket_shed_429(self, replicas, make_router):
        r = replicas()
        router = make_router(r.url, rate=1.0, burst=2.0)
        codes = [predict(router)[0] for _ in range(6)]
        assert codes.count(200) >= 1 and 429 in codes
        status, out, _, headers = predict(router)
        if status == 429:
            assert float(headers["Retry-After"]) > 0
            assert json.loads(out)["cause"] == "quota"
        assert router.statusz()["shed"] >= 1

    def test_bounded_load_spills_past_the_favorite(self, replicas, make_router):
        a, b = replicas(delay=0.05), replicas(delay=0.05)
        router = make_router(a.url, b.url, load_factor=1.0)
        errs = []

        def client():
            for _ in range(4):
                status, *_ = predict(router)
                if status != 200:
                    errs.append(status)

        threads = [threading.Thread(target=client, daemon=True) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert a.served > 0 and b.served > 0  # pressure spilled past affinity

    def test_fleet_routes_and_stats(self, replicas, make_router):
        r = replicas()
        router = make_router(r.url)
        status, out, _, _ = router.handle("GET", "/fleet/healthz", None)
        assert status == 200 and json.loads(out)["ready_replicas"] == 1
        status, out, _, _ = router.handle("GET", "/fleet/statusz", None)
        doc = json.loads(out)
        assert doc["replicas"][0]["circuit"] == "closed"
        predict(router)
        sig = router.stats()
        assert sig["ready"] == 1 and sig["window_requests"] >= 1

    def test_router_http_front_door(self, replicas, make_router):
        r = replicas()
        router = make_router(r.url)
        body = json.dumps({"model": "km", "inputs": [[1.0, 2.0]]}).encode()
        req = urllib.request.Request(
            router.url + "/v1/predict", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.getcode() == 200
            assert json.load(resp)["predictions"] == [0]


class TestSnapshotSweep:
    def test_sweep_keeps_its_cadence_for_ready_replicas(self, replicas, make_router):
        """The /canaryz, /tenantz and /decisionz sweep asks ready replicas
        only, once a period, whether or not they answer."""
        ready, warming = replicas(), replicas()
        warming.ready, warming.state = False, "warming"
        router = make_router()
        router._stop.set()  # the test polls alone
        router._poll_thread.join(timeout=5)
        router.add_replica(ready.url)
        router.add_replica(warming.url)
        router.poll_health()  # one sweep
        router.poll_health()  # inside the period: readiness only
        snaps = ["/canaryz", "/tenantz", "/decisionz"]
        assert [p for p in ready.gets if p != "/readyz"] == snaps
        assert set(warming.gets) == {"/readyz"}
        with router._lock:
            router._replicas[ready.url].sweep_ts -= router.health_period_s
        router.poll_health()
        assert [p for p in ready.gets if p != "/readyz"] == snaps * 2
        assert "tenants" in router.fleetz_report()


class TestCircuitBreaker:
    def test_eject_half_open_readmit_cycle(self, replicas, make_router):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url, cb_failures=2, cb_cooldown_s=0.3)
        assert predict(router)[0] == 200
        fav = a if a.served else b

        def circuit(url):
            return {d["url"]: d["circuit"] for d in router.statusz()["replicas"]}[url]

        fav.fail_500 = True
        for _ in range(4):
            assert predict(router)[0] == 200  # failover keeps clients green
        assert circuit(fav.url) == "open"
        assert router.statusz()["cb_ejections"] >= 1
        # ejected: the broken replica sees no traffic at all
        before = fav.served
        for _ in range(4):
            assert predict(router)[0] == 200
        assert fav.served == before
        # heal + cooldown: ONE half-open probe readmits it
        fav.fail_500 = False
        time.sleep(0.35)
        assert predict(router)[0] == 200
        assert circuit(fav.url) == "closed"
        assert router.statusz()["cb_readmissions"] >= 1

    def test_failed_probe_reopens(self, replicas, make_router):
        a, b = replicas(), replicas()
        router = make_router(a.url, b.url, cb_failures=1, cb_cooldown_s=0.2)
        assert predict(router)[0] == 200
        fav = a if a.served else b
        fav.fail_500 = True
        assert predict(router)[0] == 200  # trips the breaker via failover
        time.sleep(0.25)
        assert predict(router)[0] == 200  # probe fails, re-opens, other serves
        circuit = {d["url"]: d["circuit"] for d in router.statusz()["replicas"]}
        assert circuit[fav.url] == "open"


class TestDrain:
    def test_drained_replica_gets_no_new_work_under_load(
        self, replicas, make_router
    ):
        a, b = replicas(delay=0.03), replicas(delay=0.03)
        router = make_router(a.url, b.url)
        assert predict(router)[0] == 200
        fav = a if a.served else b
        errs = []
        stop = threading.Event()

        def client():
            while not stop.is_set():
                status, *_ = predict(router)
                if status != 200:
                    errs.append(status)

        threads = [threading.Thread(target=client, daemon=True) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        router.drain_replica(fav.url)  # no NEW work from here on
        time.sleep(0.1)
        served_at_drain = fav.served
        time.sleep(0.25)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errs  # zero client-visible failures through the drain
        assert fav.served <= served_at_drain + 3  # in-flight finished, no new stream

    def test_service_drain_finishes_inflight_work(self, tmp_path):
        # the replica-side half: a draining InferenceService answers
        # everything already admitted, then closes with zero abandons
        rng = np.random.default_rng(0)
        pts = rng.standard_normal((64, 6)).astype(np.float32)
        km = ht.cluster.KMeans(
            n_clusters=3, init="random", max_iter=5, random_state=0
        ).fit(ht.array(pts, split=0))
        d = str(tmp_path / "km")
        serving.save_model(km, d, version=1, name="km")
        svc = serving.InferenceService(max_delay_ms=5.0, max_batch=16)
        svc.load("km", d)
        svc.predict("km", pts[:2])
        results, errs = [], []

        def client():
            try:
                results.append(svc.predict("km", pts[:4], timeout=30))
            except BaseException as e:  # noqa: BLE001 - the assertion surface
                errs.append(e)

        threads = [threading.Thread(target=client, daemon=True) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.01)  # requests in the coalescer window
        assert svc.drain(timeout=10.0) is True
        for t in threads:
            t.join(timeout=10)
        assert not errs and len(results) == 4
        # drain() closes once everything admitted is answered, and close
        # lands in the declared terminal lifecycle state (the "replica"
        # machine in analysis/protocols.py: ... -> draining -> stopped)
        assert svc.state == "stopped"


# ----------------------------------------------------------------------
# readiness / liveness split
# ----------------------------------------------------------------------
class TestReadiness:
    def test_default_report_is_ready_idle(self):
        ready, doc = tserver.readiness_report()
        assert ready is True and doc["state"] == "idle"

    def test_provider_and_clear(self):
        tserver.set_readiness(lambda: (False, {"state": "warming"}))
        try:
            ready, doc = tserver.readiness_report()
            assert ready is False and doc["state"] == "warming"
            assert doc["ready"] is False
        finally:
            tserver.clear_readiness()
        assert tserver.readiness_report()[0] is True

    def test_broken_provider_reads_not_ready(self):
        def boom():
            raise RuntimeError("scripted")

        tserver.set_readiness(boom)
        try:
            ready, doc = tserver.readiness_report()
            assert ready is False and doc["state"] == "error"
            assert "scripted" in doc["error"]
        finally:
            tserver.clear_readiness()

    def test_clear_readiness_only_removes_own_provider(self):
        mine = lambda: (False, {"state": "draining"})  # noqa: E731
        theirs = lambda: (True, {"state": "ready"})  # noqa: E731
        tserver.set_readiness(mine)
        tserver.set_readiness(theirs)  # a successor took over
        tserver.clear_readiness(mine)  # must NOT clobber the successor
        try:
            assert tserver.readiness_report()[1]["state"] == "ready"
        finally:
            tserver.clear_readiness()

    def test_readyz_route_and_service_states(self, tmp_path):
        rng = np.random.default_rng(1)
        pts = rng.standard_normal((64, 6)).astype(np.float32)
        km = ht.cluster.KMeans(
            n_clusters=3, init="random", max_iter=5, random_state=0
        ).fit(ht.array(pts, split=0))
        d = str(tmp_path / "km")
        serving.save_model(km, d, version=1, name="km")
        svc = serving.InferenceService(max_delay_ms=1.0, max_batch=16)
        try:
            svc.load("km", d)
            svc.set_state("warming")
            url = svc.serve(0)
            with pytest.raises(urllib.request.HTTPError) as ei:
                urllib.request.urlopen(url + "/readyz", timeout=5)
            assert ei.value.code == 503
            doc = json.load(ei.value)
            assert doc["state"] == "warming" and doc["models"] == ["km"]
            # "idle" (liveness) and "warming" (readiness) are now distinct:
            h = svc.model_health("km")
            assert h["state"] == "warming" and h["status"] == "warming"
            assert h["healthy"] is True  # liveness unaffected
            svc.set_state("ready")
            doc = json.load(urllib.request.urlopen(url + "/readyz", timeout=5))
            assert doc["ready"] is True and doc["state"] == "ready"
            assert "misses" in doc["dispatch"]
        finally:
            svc.close()
            tserver.stop_server()

    def test_invalid_state_rejected(self):
        svc = serving.InferenceService()
        try:
            with pytest.raises(ValueError):
                svc.set_state("sleeping")
        finally:
            svc.close()


# ----------------------------------------------------------------------
# admission: queue-shed Retry-After from the measured drain rate
# ----------------------------------------------------------------------
class TestQueueRetryAfter:
    # the tenant rides the latency lane: its limit equals max_depth, so
    # the pre-QoS depth arithmetic below still holds exactly (the
    # standard lane caps at 80% of max_depth since the priority lanes)
    def test_cold_queue_shed_has_no_estimate(self):
        ac = AdmissionController(max_depth=4)
        ac.set_class("t", "latency")
        ac.admit("t", 4)
        with pytest.raises(OverloadedError) as ei:
            ac.admit("t", 2)
        assert ei.value.cause == "queue" and ei.value.retry_after_s is None

    def test_queue_shed_retry_after_tracks_drain_rate(self):
        ac = AdmissionController(max_depth=100)
        ac.set_class("t", "latency")
        # a steady drain: ~200 rows/s released over the window
        t0 = time.monotonic()
        ac.admit("t", 100)
        for _ in range(10):
            ac.release(10, "latency")
            time.sleep(0.02)
        rate = ac.drain_rate()
        assert rate > 0
        ac.admit("t", 100)  # depth back to 100
        with pytest.raises(OverloadedError) as ei:
            ac.admit("t", 50)
        got = ei.value.retry_after_s
        assert got is not None
        # excess = 100 + 50 - 100 = 50 rows at the measured rate
        assert got == pytest.approx(50.0 / rate, rel=0.5)
        assert 0.001 <= got <= 30.0
        del t0

    def test_release_prunes_window(self):
        ac = AdmissionController(max_depth=10)
        ac.admit("t", 1)
        ac.release(1)
        ac._drained.appendleft((time.monotonic() - 60.0, 1000))
        assert ac.drain_rate() < 500  # the stale entry fell out of the window


# ----------------------------------------------------------------------
# AOT executable cache
# ----------------------------------------------------------------------
@pytest.fixture
def aot_dir(tmp_path):
    d = str(tmp_path / "aot")
    prev = aot_cache.configure(d)
    yield d
    aot_cache.configure(prev)


def _dispatch_some(x=3.0):
    a = ht.array(np.full((16, 4), x, np.float32), split=0)
    b = ht.array(np.full((16, 4), 2.0, np.float32), split=0)
    return float(((a * b) + 1.0).sum().larray)


class TestAotCache:
    def test_artifact_roundtrip_across_cache_clear(self, aot_dir):
        dispatch.clear_cache()  # force a miss whatever ran before us
        s0 = aot_cache.stats()
        want = _dispatch_some()
        s1 = aot_cache.stats()
        assert s1["saves"] > s0["saves"]
        dispatch.clear_cache()  # a "fresh process" for the in-memory cache
        got = _dispatch_some()
        s2 = aot_cache.stats()
        assert got == want
        assert s2["hits"] > s1["hits"]  # loaded from disk, not compiled

    def test_corrupt_artifact_falls_back_and_heals(self, aot_dir):
        from heat_tpu.resilience.atomic import verify_checksum

        dispatch.clear_cache()
        want = _dispatch_some()
        files = [f for f in os.listdir(aot_dir) if f.endswith(".aotx")]
        assert files
        path = os.path.join(aot_dir, files[0])
        with open(path, "r+b") as f:
            f.seek(50)
            f.write(b"CORRUPTCORRUPT")
        s0 = aot_cache.stats()
        dispatch.clear_cache()
        assert _dispatch_some() == want  # fresh compile, right answer
        s1 = aot_cache.stats()
        assert s1["errors"] > s0["errors"]
        assert s1["saves"] > s0["saves"]  # dropped, recompiled, re-written
        assert verify_checksum(path) is True  # the healed artifact is whole

    def test_stale_fingerprint_recompiles(self, aot_dir):
        import pickle

        dispatch.clear_cache()
        want = _dispatch_some()
        files = [f for f in os.listdir(aot_dir) if f.endswith(".aotx")]
        path = os.path.join(aot_dir, files[0])
        with open(path, "rb") as f:
            doc = pickle.load(f)
        doc["fingerprint"] = "jax=0.0.0;backend=tpu;device=v9;n=4096"
        with atomic_write(path) as tmp:
            with open(tmp, "wb") as f:
                pickle.dump(doc, f)
        write_checksum(path)
        s0 = aot_cache.stats()
        dispatch.clear_cache()
        assert _dispatch_some() == want
        s1 = aot_cache.stats()
        assert s1["stale"] > s0["stale"]  # ignored, not an error

    def test_unstable_keys_are_refused_not_persisted(self, aot_dir):
        s0 = aot_cache.stats()
        out = dispatch.eager_apply(lambda x: x + 1, (np.ones((4,), np.float32),))
        assert float(np.asarray(out)[0]) == 2.0
        s1 = aot_cache.stats()
        assert s1["unkeyed"] > s0["unkeyed"]
        assert s1["saves"] == s0["saves"]  # a lambda key must never alias on disk

    def test_stable_key_deterministic_and_distinct(self):
        import jax.numpy as jnp

        key_a = ("apply", jnp.add, (), ((4, 4), np.dtype(np.float32), None))
        key_b = ("apply", jnp.multiply, (), ((4, 4), np.dtype(np.float32), None))
        assert aot_cache.stable_key(key_a) == aot_cache.stable_key(key_a)
        assert aot_cache.stable_key(key_a) != aot_cache.stable_key(key_b)
        assert aot_cache.stable_key(("x", lambda: 0)) is None

    def test_disarmed_cache_writes_nothing(self, tmp_path):
        assert not aot_cache.enabled() or aot_cache.stats()["directory"]
        prev = aot_cache.configure(None)
        try:
            s0 = aot_cache.stats()
            _dispatch_some(5.0)
            assert aot_cache.stats()["saves"] == s0["saves"]
        finally:
            aot_cache.configure(prev)


# ----------------------------------------------------------------------
# pre-warm manifest
# ----------------------------------------------------------------------
class TestPrewarm:
    @pytest.fixture
    def svc(self, tmp_path):
        rng = np.random.default_rng(2)
        pts = rng.standard_normal((64, 6)).astype(np.float32)
        km = ht.cluster.KMeans(
            n_clusters=3, init="random", max_iter=5, random_state=0
        ).fit(ht.array(pts, split=0))
        d = str(tmp_path / "km")
        serving.save_model(km, d, version=1, name="km")
        svc = serving.InferenceService(max_delay_ms=1.0, max_batch=16)
        svc.load("km", d)
        svc._test_pts = pts
        yield svc
        svc.close()

    def test_manifest_records_live_bucket_shapes(self, svc, tmp_path):
        pts = svc._test_pts
        for n in (1, 3, 9):
            svc.predict("km", pts[:n])
        path = str(tmp_path / "prewarm.json")
        doc = svc.export_prewarm_manifest(path)
        buckets = {e["bucket"] for e in doc["entries"]}
        assert buckets == {1, 4, 16}  # the pad-to-bucket shapes, not raw sizes
        assert all(e["model"] == "km" and e["features"] == 6 for e in doc["entries"])
        assert os.path.exists(path) and os.path.exists(path + ".crc32")
        assert svc.load_prewarm_manifest(path) == doc

    def test_prewarm_reaches_hit_rate_one_before_first_request(
        self, svc, tmp_path, aot_dir
    ):
        pts = svc._test_pts
        dispatch.clear_cache()  # the warm-up predicts must miss and save
        for n in (1, 3, 9):
            svc.predict("km", pts[:n])
        manifest = svc.export_prewarm_manifest()
        dispatch.clear_cache()  # fresh-replica simulation
        report = svc.prewarm(manifest)
        assert report["warmed"] == 3
        assert report["new_compiles"] == 0  # every program came off disk
        assert report["aot_hits"] >= 3
        s0 = dispatch.cache_stats()
        svc.predict("km", pts[:3])  # the first "real" request
        s1 = dispatch.cache_stats()
        assert s1["misses"] == s0["misses"]  # zero compiles after warm
        assert s1["hits"] > s0["hits"]

    def test_prewarm_skips_unknown_models(self, svc):
        report = svc.prewarm(
            {"entries": [{"model": "ghost", "bucket": 4, "features": 6}]}
        )
        assert report == {
            "warmed": 0, "skipped": 1, "new_compiles": 0, "aot_hits": 0,
        }


# ----------------------------------------------------------------------
# autoscaler hysteresis (stubbed actuator)
# ----------------------------------------------------------------------
class _StubRouter:
    def __init__(self):
        self.added, self.drained, self.removed = [], [], []
        self.signal = {}

    def stats(self):
        return dict(self.signal)

    def add_replica(self, url):
        self.added.append(url)

    def drain_replica(self, url):
        self.drained.append(url)

    def remove_replica(self, url):
        self.removed.append(url)

    def replica_urls(self):
        return list(self.added)


class _StubReplicaSet:
    def __init__(self):
        self._urls = []
        self.stopped = []
        self.spawned = 0

    def spawn(self):
        self.spawned += 1
        url = f"http://fake:{8000 + self.spawned}"
        self._urls.append(url)
        return url

    def drain_stop(self, url, **kw):
        self._urls.remove(url)
        self.stopped.append(url)
        return 0

    def urls(self):
        return list(self._urls)


def _sig(replicas, ready=None, p99=5.0, per_ready=0.0, shed=0, nr=0, reqs=10):
    return {
        "replicas": replicas,
        "ready": replicas if ready is None else ready,
        "p99_ms": p99,
        "inflight_per_ready": per_ready,
        "shed": shed,
        "no_replica_503": nr,
        "window_requests": reqs,
    }


class TestAutoscaler:
    def make(self, **kw):
        router, rs = _StubRouter(), _StubReplicaSet()
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 4)
        kw.setdefault("up_ticks", 2)
        kw.setdefault("down_ticks", 3)
        kw.setdefault("p99_up_ms", 50.0)
        kw.setdefault("p99_down_ms", 10.0)
        kw.setdefault("inflight_up", 8.0)
        kw.setdefault("inflight_down", 1.0)
        return FleetAutoscaler(router, rs, **kw), router, rs

    def test_scale_up_needs_consecutive_overloaded_ticks(self):
        scaler, router, rs = self.make()
        assert scaler.evaluate(_sig(2, p99=100.0)) is None  # 1st breach: wait
        assert scaler.evaluate(_sig(2, p99=5.0)) is None  # breach cleared
        assert scaler.evaluate(_sig(2, p99=100.0)) is None  # streak restarts
        assert scaler.evaluate(_sig(2, p99=100.0)) == "up"

    def test_scale_up_bounded_by_max(self):
        scaler, router, rs = self.make(max_replicas=2)
        for _ in range(6):
            assert scaler.evaluate(_sig(2, p99=500.0)) is None  # at the ceiling

    def test_shed_delta_counts_overloaded(self):
        scaler, router, rs = self.make(up_ticks=1)
        scaler.evaluate(_sig(2, shed=0))
        assert scaler.evaluate(_sig(2, shed=5)) == "up"
        # an unchanged cumulative counter is NOT a fresh shed
        assert scaler.evaluate(_sig(2, shed=5)) is None or True

    def test_scale_down_needs_streak_and_floor(self):
        scaler, router, rs = self.make(down_ticks=3, min_replicas=2)
        quiet = _sig(3, p99=2.0, per_ready=0.0)
        assert scaler.evaluate(quiet) is None
        assert scaler.evaluate(quiet) is None
        assert scaler.evaluate(quiet) == "down"
        # at the floor: stays
        calm = _sig(2, p99=2.0)
        for _ in range(5):
            assert scaler.evaluate(calm) is None

    def test_mixed_tick_resets_both_streaks(self):
        scaler, router, rs = self.make(up_ticks=2, down_ticks=2)
        assert scaler.evaluate(_sig(2, p99=100.0)) is None
        # neither overloaded nor underloaded (p99 between the watermarks)
        assert scaler.evaluate(_sig(2, p99=30.0)) is None
        assert scaler.evaluate(_sig(2, p99=100.0)) is None  # streak was reset
        assert scaler.evaluate(_sig(2, p99=2.0)) is None

    def test_tick_actuates_spawn_and_drain_order(self):
        scaler, router, rs = self.make(up_ticks=1, down_ticks=2, min_replicas=1)
        router.signal = _sig(1, p99=100.0)
        assert scaler.tick() == "up"
        assert rs.spawned == 1 and router.added == rs.urls()
        router.signal = _sig(2, p99=1.0)
        scaler.tick()
        assert scaler.tick() == "down"
        # drain from routing BEFORE stopping the process, then remove
        assert router.drained == rs.stopped == router.removed
        assert scaler.state()["action"] == "down"

    def test_bounds_validated(self):
        with pytest.raises(ValueError):
            self.make(min_replicas=3, max_replicas=2)


# ----------------------------------------------------------------------
# the real replica lifecycle (one subprocess round trip)
# ----------------------------------------------------------------------
class TestReplicaLifecycle:
    def test_spawn_prewarm_route_drain(self, tmp_path):
        rng = np.random.default_rng(3)
        pts = rng.standard_normal((128, 6)).astype(np.float32)
        km = ht.cluster.KMeans(
            n_clusters=3, init="random", max_iter=5, random_state=0
        ).fit(ht.array(pts, split=0))
        mdir = str(tmp_path / "km")
        serving.save_model(km, mdir, version=1, name="km")
        manifest = str(tmp_path / "prewarm.json")
        with open(manifest, "w") as f:
            json.dump({"version": 1, "entries": [
                {"model": "km", "bucket": b, "features": 6, "dtype": "float32"}
                for b in (1, 4)
            ]}, f)
        rs = LocalReplicaSet(
            {"km": mdir}, str(tmp_path / "fleet"),
            aot_cache=str(tmp_path / "aot"), prewarm=manifest,
            max_batch=8, max_delay_ms=1.0,
        )
        router = FleetRouter(health_period_s=0.2)
        try:
            url = rs.spawn()
            doc = json.load(urllib.request.urlopen(url + "/readyz", timeout=5))
            assert doc["ready"] is True and doc["models"] == ["km"]
            assert doc["aot"]["saves"] >= 2  # it populated the fleet cache
            router.add_replica(url)
            router.poll_health()
            body = json.dumps(
                {"model": "km", "inputs": pts[:3].tolist()}
            ).encode()
            status, out, _, _ = router.handle("POST", "/v1/predict", body)
            assert status == 200
            assert len(json.loads(out)["predictions"]) == 3
            rc = rs.drain_stop(url)
            assert rc == 0  # SIGTERM drained cleanly
            assert "drained cleanly: True" in rs._tail(
                os.path.join(str(tmp_path / "fleet"), "replica-0.log")
            )
        finally:
            router.close()
            rs.close()
