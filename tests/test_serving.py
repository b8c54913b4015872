"""Online serving layer: model registry, request coalescing, admission
control, and the /v1 HTTP surface.

The acceptance properties (ISSUE 9): save -> hot-load -> predict is
bitwise-identical per estimator (including a cross-world P != Q
restore), steady-state traffic triggers zero new compiles across varied
batch sizes (pad-to-bucket), over-quota tenants shed with a typed 429
while admitted traffic keeps its latency, and promote/rollback swap
versions with zero downtime.
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
from heat_tpu import serving
from heat_tpu.core import dispatch
from heat_tpu.resilience import OverloadedError, ReshapeError, faults
from heat_tpu.serving import model_io
from heat_tpu.serving.admission import AdmissionController, TokenBucket
from heat_tpu.serving.coalescer import ModelBatcher
from heat_tpu.telemetry import metrics as tm
from heat_tpu.telemetry import server as tserver
from heat_tpu.utils.checkpoint import Checkpointer

RNG = np.random.default_rng(0)
PTS = RNG.standard_normal((120, 6)).astype(np.float32)
LABELS = RNG.integers(0, 3, 120).astype(np.int64)
YREG = (PTS @ RNG.standard_normal(6) + 0.5).astype(np.float32)

ALL_KINDS = list(model_io.SUPPORTED_KINDS)


def _fit(kind):
    x = ht.array(PTS, split=0)
    if kind == "KMeans":
        return ht.cluster.KMeans(n_clusters=3, init="random", max_iter=5, random_state=0).fit(x)
    if kind == "KMedians":
        return ht.cluster.KMedians(n_clusters=3, init="random", max_iter=5, random_state=0).fit(x)
    if kind == "KMedoids":
        return ht.cluster.KMedoids(n_clusters=3, init="random", max_iter=5, random_state=0).fit(x)
    if kind == "PCA":
        return ht.decomposition.PCA(n_components=3).fit(x)
    if kind == "Lasso":
        return ht.regression.Lasso(lam=0.05, max_iter=20).fit(x, ht.array(YREG.reshape(-1, 1), split=0))
    if kind == "KNeighborsClassifier":
        return ht.classification.KNeighborsClassifier(n_neighbors=3).fit(x, ht.array(LABELS, split=0))
    raise AssertionError(kind)


@pytest.fixture
def fitted_kmeans():
    return _fit("KMeans")


@pytest.fixture
def kmeans_dir(tmp_path, fitted_kmeans):
    d = str(tmp_path / "km")
    serving.save_model(fitted_kmeans, d, version=1, name="km")
    return d


# ----------------------------------------------------------------------
# model codec: save -> hot-load -> predict equivalence grid
# ----------------------------------------------------------------------
class TestModelCodec:
    @pytest.mark.parametrize("kind", ALL_KINDS)
    def test_hot_load_predict_bitwise(self, kind, tmp_path):
        est = _fit(kind)
        d = str(tmp_path / kind)
        serving.save_model(est, d, version=1)
        xt = ht.array(PTS[:16], split=None)
        ref = model_io.infer(est, xt).numpy()
        reg = serving.ModelRegistry()
        reg.load(kind, d)
        got = model_io.infer(reg.get(kind), xt).numpy()
        assert got.dtype == ref.dtype
        assert np.array_equal(ref, got), f"{kind} restored predictions differ"

    @pytest.mark.parametrize("kind", ALL_KINDS)
    def test_cross_world_restore_bitwise(self, kind, tmp_path):
        """Fitted at world P (the test mesh), served at world Q != P."""
        est = _fit(kind)
        d = str(tmp_path / kind)
        serving.save_model(est, d, version=1)
        ref = model_io.infer(est, ht.array(PTS[:16], split=None)).numpy()
        w = ht.get_comm()
        q = 3 if w.size != 3 else 2
        c3 = w.reshape(q)
        before = tm.counter("checkpoint.crossworld_restores").value
        reg = serving.ModelRegistry(comm=c3)
        reg.load(kind, d)
        assert tm.counter("checkpoint.crossworld_restores").value == before + 1
        got = model_io.infer(
            reg.get(kind), ht.array(PTS[:16], split=None, comm=c3)
        ).numpy()
        assert np.array_equal(ref, got), f"{kind} cross-world predictions differ"

    def test_unfitted_estimator_refused(self):
        with pytest.raises(model_io.NotFittedError):
            model_io.export_state(ht.cluster.KMeans(n_clusters=2))

    def test_unsupported_estimator_refused(self):
        with pytest.raises(TypeError, match="supported estimator kinds"):
            model_io.export_state(object())

    def test_non_model_checkpoint_refused(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(0, {"not": "a model"})
        with pytest.raises(ValueError, match="serving model document"):
            serving.ModelRegistry().load("x", str(tmp_path))

    def test_metadata_written(self, kmeans_dir):
        ck = Checkpointer(kmeans_dir)
        meta = ck.metadata(1)
        assert meta["kind"] == "KMeans" and meta["name"] == "km"


# ----------------------------------------------------------------------
# registry: versions, promote/rollback, async load, template validation
# ----------------------------------------------------------------------
class TestRegistry:
    def test_versions_promote_rollback(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        est2 = _fit("KMedians")
        serving.save_model(est2, d, version=2)
        reg = serving.ModelRegistry()
        assert reg.load("m", d, version=1) == 1
        assert reg.active_version("m") == 1
        assert reg.load("m", d, version=2) == 2  # load+activate
        assert reg.active_version("m") == 2
        assert type(reg.get("m")).__name__ == "KMedians"
        assert reg.rollback("m") == 1
        assert type(reg.get("m")).__name__ == "KMeans"
        reg.promote("m", 2)
        assert reg.active_version("m") == 2
        listing = reg.models()["m"]
        assert listing["active"] == 2 and set(listing["versions"]) == {"1", "2"}

    def test_canary_load_without_activation(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        serving.save_model(fitted_kmeans, d, version=2)
        reg = serving.ModelRegistry()
        reg.load("m", d, version=1)
        reg.load("m", d, version=2, activate=False)
        assert reg.active_version("m") == 1  # canary resident, not active
        reg.promote("m", 2)
        assert reg.active_version("m") == 2

    def test_unload_active_refused(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        reg = serving.ModelRegistry()
        reg.load("m", d)
        with pytest.raises(ValueError, match="active"):
            reg.unload("m", 1)
        reg.unload("m")  # whole model is fine
        with pytest.raises(KeyError):
            reg.get("m")

    def test_template_validation_raises_reshape_error(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        bad = model_io.export_state(fitted_kmeans)
        bad["state"] = {"cluster_centers": np.zeros((7, 99), np.float32)}
        with pytest.raises(ReshapeError):
            serving.ModelRegistry().load("m", d, template=bad)

    def test_async_load_swaps_atomically(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        reg = serving.ModelRegistry()
        handle = reg.load_async("m", d)
        assert handle.wait(30) == 1
        assert reg.active_version("m") == 1
        reg.close()

    def test_async_load_error_surfaces_and_old_version_serves(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        reg = serving.ModelRegistry()
        reg.load("m", d)
        handle = reg.load_async("m", str(tmp_path / "missing"))
        with pytest.raises(FileNotFoundError):
            handle.wait(30)
        # the pending error also re-raises at the next close/wait ...
        with pytest.raises(FileNotFoundError):
            reg.close()
        # ... and the active version never stopped serving
        assert reg.active_version("m") == 1
        model_io.infer(reg.get("m"), ht.array(PTS[:4], split=None))

    def test_load_fault_site_scripted(self, tmp_path, fitted_kmeans):
        d = str(tmp_path / "m")
        serving.save_model(fitted_kmeans, d, version=1)
        reg = serving.ModelRegistry()
        reg.load("m", d)
        with faults.fault_plan({"serve.load": [{"at": 0, "kind": "permanent"}]}):
            with pytest.raises(Exception):
                reg.load("m", d)
        assert reg.active_version("m") == 1  # survivor keeps serving


# ----------------------------------------------------------------------
# batch buckets
# ----------------------------------------------------------------------
class TestBatchBucket:
    def test_padding_grid(self):
        assert [dispatch.batch_bucket(n) for n in (1, 2, 3, 5, 8, 9, 33)] == [
            1, 2, 4, 8, 8, 16, 64,
        ]

    def test_cap_is_a_bucket(self):
        assert dispatch.batch_bucket(40, cap=48) == 48
        assert dispatch.batch_bucket(48, cap=48) == 48
        assert dispatch.batch_bucket(3, cap=48) == 4

    def test_bad_inputs(self):
        with pytest.raises(ValueError):
            dispatch.batch_bucket(0)
        with pytest.raises(ValueError):
            dispatch.batch_bucket(65, cap=64)


# ----------------------------------------------------------------------
# coalescer
# ----------------------------------------------------------------------
class TestCoalescer:
    def _echo_batcher(self, max_batch=32, max_delay_s=0.05, calls=None):
        def infer(rows):
            if calls is not None:
                calls.append(rows.shape[0])
            return rows * 2.0

        return ModelBatcher("echo", infer, max_batch=max_batch, max_delay_s=max_delay_s)

    def test_concurrent_requests_coalesce_and_scatter(self):
        calls = []
        b = self._echo_batcher(calls=calls)
        results = {}

        def client(i):
            rows = np.full((1 + i % 3, 4), float(i), np.float32)
            results[i] = (rows, b.submit(rows, timeout=30))

        threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.close()
        for rows, out in results.values():
            assert np.array_equal(out, rows * 2.0)  # each caller got ITS slice
        total_rows = sum(r.shape[0] for r, _ in results.values())
        assert sum(calls) >= total_rows  # bucket padding may add rows
        assert len(calls) < 12  # genuinely coalesced

    def test_batches_are_bucket_padded(self):
        calls = []
        b = self._echo_batcher(calls=calls, max_delay_s=0.0)
        b.submit(np.ones((3, 4), np.float32), timeout=30)
        b.submit(np.ones((5, 4), np.float32), timeout=30)
        b.close()
        assert all((c & (c - 1)) == 0 for c in calls), calls  # powers of two

    def test_inference_error_delivered_to_all_waiters(self):
        def boom(rows):
            raise RuntimeError("kaboom")

        b = ModelBatcher("bad", boom, max_batch=16, max_delay_s=0.0)
        with pytest.raises(RuntimeError, match="kaboom"):
            b.submit(np.ones((2, 2), np.float32), timeout=30)
        assert b.alive()  # the batcher thread survived the error
        b.close()

    def test_batch_fault_site_scripted(self):
        b = self._echo_batcher()
        with faults.fault_plan({"serve.batch": [{"at": 0, "kind": "transient"}]}):
            with pytest.raises(OSError):
                b.submit(np.ones((1, 2), np.float32), timeout=30)
        # next batch is clean
        out = b.submit(np.ones((1, 2), np.float32), timeout=30)
        assert np.array_equal(out, np.full((1, 2), 2.0, np.float32))
        b.close()

    def test_oversized_request_rejected(self):
        b = self._echo_batcher(max_batch=8)
        with pytest.raises(ValueError, match="max batch"):
            b.submit(np.ones((9, 2), np.float32))
        b.close()

    def test_submit_after_close_raises(self):
        b = self._echo_batcher()
        b.close()
        with pytest.raises(RuntimeError, match="closed"):
            b.submit(np.ones((1, 2), np.float32))

    def test_close_drains_queued_requests(self):
        b = self._echo_batcher(max_delay_s=5.0)  # long tick: requests queue up
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault("r", b.submit(np.ones((2, 2), np.float32), timeout=30))
        )
        t.start()
        time.sleep(0.05)
        b.close()  # must answer the queued request, not strand it
        t.join(30)
        assert "r" in out and np.array_equal(out["r"], np.full((2, 2), 2.0, np.float32))


# ----------------------------------------------------------------------
# admission control
# ----------------------------------------------------------------------
class TestAdmission:
    def test_token_bucket_refill_math(self):
        tb = TokenBucket(rate=10.0, burst=5.0)
        now = time.monotonic()
        assert tb.take(5, now) == 0.0  # burst spent
        wait = tb.take(1, now)
        assert wait == pytest.approx(0.1, rel=1e-6)  # 1 token @ 10/s
        assert tb.take(1, now + 0.2) == 0.0  # refilled

    def test_unlimited_default(self):
        tb = TokenBucket(rate=0.0, burst=1.0)
        assert all(tb.take(100) == 0.0 for _ in range(10))

    def test_quota_shed_with_retry_after(self):
        ac = AdmissionController(max_depth=100)
        ac.set_quota("t", rate=1.0, burst=2.0)
        ac.admit("t", 2)
        with pytest.raises(OverloadedError) as ei:
            ac.admit("t", 2)
        assert ei.value.cause == "quota" and ei.value.retry_after_s > 0
        assert ei.value.tenant == "t"

    def test_queue_depth_shed_and_release(self):
        ac = AdmissionController(max_depth=4)
        ac.admit("a", 3)
        with pytest.raises(OverloadedError) as ei:
            ac.admit("b", 2)
        assert ei.value.cause == "queue"
        ac.release(3)
        ac.admit("b", 2)  # capacity came back
        assert ac.depth() == 2

    def test_tenants_are_isolated(self):
        ac = AdmissionController(max_depth=1000)
        ac.set_quota("cheap", rate=0.001, burst=1.0)
        ac.admit("cheap", 1)
        with pytest.raises(OverloadedError):
            ac.admit("cheap", 1)
        for _ in range(20):  # the default (unlimited) tenant is unaffected
            ac.admit("rich", 1)


# ----------------------------------------------------------------------
# the composed service
# ----------------------------------------------------------------------
class TestService:
    def test_predict_matches_direct(self, kmeans_dir, fitted_kmeans):
        with serving.InferenceService(max_delay_ms=0.5) as svc:
            svc.load("km", kmeans_dir)
            got = svc.predict("km", PTS[:7])
            ref = model_io.infer(
                fitted_kmeans, ht.array(np.concatenate([PTS[:7], np.zeros((1, 6), np.float32)]), split=None)
            ).numpy()[:7]
            assert np.array_equal(got, ref)

    def test_single_row_request(self, kmeans_dir):
        with serving.InferenceService(max_delay_ms=0.5) as svc:
            svc.load("km", kmeans_dir)
            out = svc.predict("km", PTS[0])
            assert out.shape == (1,)

    def test_steady_state_zero_new_compiles(self, kmeans_dir):
        with serving.InferenceService(max_delay_ms=0.5, max_batch=64) as svc:
            svc.load("km", kmeans_dir)
            for b in (1, 2, 4, 8, 16, 32, 64):  # warm every bucket
                svc.predict("km", PTS[:b])
            s0 = dispatch.cache_stats()
            for n in (3, 7, 1, 12, 30, 64, 5, 9, 17, 33):
                svc.predict("km", PTS[:n])
            s1 = dispatch.cache_stats()
            assert s1["misses"] == s0["misses"], "steady-state serving compiled"
            assert s1["hits"] > s0["hits"]

    def test_hot_swap_promote_rollback_zero_downtime(self, tmp_path):
        km = _fit("KMeans")
        d = str(tmp_path / "m")
        serving.save_model(km, d, version=1)
        est2 = _fit("PCA")
        serving.save_model(est2, d, version=2)
        with serving.InferenceService(max_delay_ms=0.5) as svc:
            svc.load("m", d, version=1)
            out1 = svc.predict("m", PTS[:4])
            assert out1.dtype.kind == "i"  # labels
            svc.load("m", d, version=2)  # hot swap to the PCA
            out2 = svc.predict("m", PTS[:4])
            assert out2.dtype.kind == "f" and out2.shape == (4, 3)  # transform
            svc.registry.rollback("m")
            out3 = svc.predict("m", PTS[:4])
            assert np.array_equal(out3, out1)

    def test_unknown_model_keyerror(self, kmeans_dir):
        with serving.InferenceService() as svc:
            with pytest.raises(KeyError, match="unknown model"):
                svc.predict("nope", PTS[:2])

    def test_quota_shed_does_not_block_others(self, kmeans_dir):
        with serving.InferenceService(max_delay_ms=0.5) as svc:
            svc.load("km", kmeans_dir)
            svc.set_quota("cheap", rate=0.001, burst=2.0)
            shed_before = tm.counter("serving.shed_quota").value
            svc.predict("km", PTS[:2], tenant="cheap")
            with pytest.raises(OverloadedError):
                svc.predict("km", PTS[:2], tenant="cheap")
            assert tm.counter("serving.shed_quota").value == shed_before + 1
            for _ in range(3):  # in-quota tenant unaffected
                svc.predict("km", PTS[:4], tenant="rich")

    def test_service_turns_cost_accounting_on(self):
        """Tenant metering bills analyzed cost, which a compile records
        only with cost accounting on: a service turns it on."""
        prev = dispatch.set_cost_accounting(False)
        try:
            with serving.InferenceService():
                assert dispatch.cost_accounting_enabled()
        finally:
            dispatch.set_cost_accounting(prev)

    def test_latency_histogram_populated(self, kmeans_dir):
        with serving.InferenceService(max_delay_ms=0.5) as svc:
            svc.load("km", kmeans_dir)
            before = tm.histogram("serving.latency_ms").count
            svc.predict("km", PTS[:2])
            assert tm.histogram("serving.latency_ms").count == before + 1


# ----------------------------------------------------------------------
# HTTP surface + the route-registry hook
# ----------------------------------------------------------------------
@pytest.fixture
def http_service(kmeans_dir):
    tserver.stop_server()
    svc = serving.InferenceService(max_delay_ms=0.5)
    svc.load("km", kmeans_dir)
    url = svc.serve(0)
    yield svc, url
    svc.close()
    tserver.stop_server()


def _get(url, timeout=10):
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, json.loads(r.read() or b"null"), dict(r.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else None), dict(e.headers)


def _post(url, doc, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read() or b"null"), dict(r.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else None), dict(e.headers)


class TestHTTP:
    def test_models_listing(self, http_service):
        _, url = http_service
        code, doc, _ = _get(f"{url}/v1/models")
        assert code == 200
        assert doc["models"]["km"]["active"] == 1
        assert doc["models"]["km"]["versions"]["1"]["kind"] == "KMeans"

    def test_predict_roundtrip(self, http_service, fitted_kmeans):
        svc, url = http_service
        code, doc, _ = _post(f"{url}/v1/predict", {"model": "km", "inputs": PTS[:3].tolist()})
        assert code == 200
        assert doc["model"] == "km" and doc["version"] == 1 and doc["n"] == 3
        direct = svc.predict("km", PTS[:3])
        assert np.array_equal(np.asarray(doc["predictions"]), direct)

    def test_predict_unknown_model_404(self, http_service):
        _, url = http_service
        code, doc, _ = _post(f"{url}/v1/predict", {"model": "nope", "inputs": [[1.0] * 6]})
        assert code == 404 and "unknown model" in doc["error"]

    def test_predict_bad_payload_400(self, http_service):
        _, url = http_service
        code, _, _ = _post(f"{url}/v1/predict", {"inputs": [[1.0]]})
        assert code == 400
        req = urllib.request.Request(
            f"{url}/v1/predict", data=b"not json", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400

    def test_over_quota_429_with_retry_after(self, http_service):
        svc, url = http_service
        svc.set_quota("cheap", rate=0.001, burst=2.0)
        body = {"model": "km", "inputs": PTS[:2].tolist(), "tenant": "cheap"}
        code, _, _ = _post(f"{url}/v1/predict", body)
        assert code == 200
        code, doc, headers = _post(f"{url}/v1/predict", body)
        assert code == 429
        assert doc["cause"] == "quota"
        assert float(headers["Retry-After"]) > 0
        # in-quota traffic still lands
        code, _, _ = _post(
            f"{url}/v1/predict", {"model": "km", "inputs": PTS[:2].tolist()}
        )
        assert code == 200

    def test_per_model_healthz(self, http_service):
        _, url = http_service
        code, doc, _ = _get(f"{url}/v1/models/km/healthz")
        assert code == 200 and doc["status"] in ("ok", "idle") and doc["version"] == 1
        code, _, _ = _get(f"{url}/v1/models/ghost/healthz")
        assert code == 404

    def test_builtin_routes_still_served(self, http_service):
        _, url = http_service
        assert _get(f"{url}/healthz")[0] in (200, 503)
        r = urllib.request.urlopen(f"{url}/metrics", timeout=10)
        assert b"serving" in r.read()


class TestRouteRegistry:
    def teardown_method(self):
        tserver.unregister_route("/echo/")
        tserver.unregister_route("/echo/deep/")
        tserver.stop_server()

    def test_register_dispatch_unregister(self):
        tserver.stop_server()
        hits = []

        def handler(method, path, body):
            hits.append((method, path, body))
            return 200, "text/plain", "pong"

        tserver.register_route("/echo/", handler)
        srv = tserver.start_server(0)
        r = urllib.request.urlopen(f"{srv.url}/echo/x", timeout=10)
        assert r.read() == b"pong"
        req = urllib.request.Request(f"{srv.url}/echo/x", data=b"hi", method="POST")
        urllib.request.urlopen(req, timeout=10)
        assert ("GET", "/echo/x", None) in hits and ("POST", "/echo/x", b"hi") in hits
        tserver.unregister_route("/echo/")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/echo/x", timeout=10)
        assert ei.value.code == 404

    def test_longest_prefix_wins(self):
        tserver.stop_server()
        tserver.register_route("/echo/", lambda m, p, b: (200, "text/plain", "shallow"))
        tserver.register_route("/echo/deep/", lambda m, p, b: (200, "text/plain", "deep"))
        srv = tserver.start_server(0)
        assert urllib.request.urlopen(f"{srv.url}/echo/deep/x", timeout=10).read() == b"deep"
        assert urllib.request.urlopen(f"{srv.url}/echo/y", timeout=10).read() == b"shallow"
        assert tserver.registered_routes()[0] == "/echo/deep/"

    def test_routes_survive_server_restart(self):
        tserver.stop_server()
        tserver.register_route("/echo/", lambda m, p, b: (200, "text/plain", "pong"))
        srv = tserver.start_server(0)
        assert urllib.request.urlopen(f"{srv.url}/echo/", timeout=10).read() == b"pong"
        tserver.stop_server()
        tserver.stop_server()  # close() stays idempotent
        srv2 = tserver.start_server(0)
        assert urllib.request.urlopen(f"{srv2.url}/echo/", timeout=10).read() == b"pong"

    def test_handler_error_is_500_and_server_survives(self):
        tserver.stop_server()

        def bad(method, path, body):
            raise RuntimeError("handler bug")

        tserver.register_route("/echo/", bad)
        srv = tserver.start_server(0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/echo/", timeout=10)
        assert ei.value.code == 500
        assert urllib.request.urlopen(f"{srv.url}/metrics", timeout=10).status == 200

    def test_bad_prefix_rejected(self):
        with pytest.raises(ValueError):
            tserver.register_route("echo", lambda m, p, b: (200, "t", ""))


# ----------------------------------------------------------------------
# kill-and-restore: a model fitted at world P serves at world Q
# ----------------------------------------------------------------------
_FIT_AT_P_SOURCE = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import heat_tpu as ht
from heat_tpu import serving

rng = np.random.default_rng(7)
pts = rng.standard_normal((96, 5)).astype(np.float32)
x = ht.array(pts, split=0)
km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=6, random_state=1).fit(x)
d = sys.argv[1]
serving.save_model(km, d, version=3, name="km4")
preds = serving.model_io.infer(km, ht.array(pts[:24], split=None)).numpy()
np.save(os.path.join(d, "preds.npy"), preds)
np.save(os.path.join(d, "pts.npy"), pts)
assert ht.get_comm().size == 4
os._exit(0)  # hard exit: the model store must already be durable
"""


class TestCrossWorldServing:
    def test_model_fitted_at_p_serves_at_q(self, tmp_path):
        d = str(tmp_path / "store")
        os.makedirs(d)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", _FIT_AT_P_SOURCE, d],
            capture_output=True, text=True, env=env, timeout=280,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        pts = np.load(os.path.join(d, "pts.npy"))
        ref = np.load(os.path.join(d, "preds.npy"))
        ck = Checkpointer(d)
        assert ck.world_size(3) == 4  # fitted at world P=4
        with serving.InferenceService(max_delay_ms=0.5) as svc:  # serves at Q=8
            v = svc.load("km4", d)
            assert v == 3
            rec = svc.registry.record("km4")
            assert rec["world_size_written"] == 4
            assert rec["world_size_serving"] == ht.get_comm().size != 4
            got = np.concatenate(
                [svc.predict("km4", pts[i : i + 8]) for i in range(0, 24, 8)]
            )
            assert np.array_equal(got, ref)
            # and the /healthz doc reports the cross-world provenance
            health = svc.model_health("km4")
            assert health["world_size_written"] == 4
            assert health["healthy"]
