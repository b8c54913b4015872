"""Inference service: registry + coalescer + admission behind one surface.

:class:`InferenceService` is the composition the serving design doc
draws: a request enters through :meth:`~InferenceService.predict`
(Python) or ``POST /v1/predict`` (HTTP), passes **admission control**
(per-tenant quota, bounded depth — shed with
:class:`~heat_tpu.resilience.errors.OverloadedError`/429, never
queued-to-collapse), lands in its model's **coalescer** queue, rides a
padded **bucket** batch through the executable cache, and returns with
its slice of the batch result.

Every request runs under a **trace**
(:mod:`heat_tpu.telemetry.tracing`): one ``trace_id`` stamps the
``serve.request`` root, the per-stage spans (admission → coalesce_wait →
pad → dispatch → execute → scatter, across the request and batcher
threads), and any nested compile/comm spans.  End-to-end latency lands
in ``serving.latency_ms`` and each stage in its
``serving.stage.{stage}_ms`` histogram — bucket exemplars carry the
most recent trace_id, so a ``/metrics`` latency bucket links to the
concrete request retained in ``/tracez``; shed and errored requests are
always retained there.

HTTP surface (mounted on the telemetry introspection server through
:func:`~heat_tpu.telemetry.server.register_route` — one process, one
port):

=====================================  ================================
route                                  payload
=====================================  ================================
``GET /v1/models``                     registry listing: versions,
                                       active pointer, rollback history
``POST /v1/predict``                   ``{"model", "inputs", "tenant"?,
                                       "version"?}`` -> predictions
``GET /v1/models/<name>/healthz``      per-model liveness: loaded
                                       version, batcher thread alive,
                                       queue depth, last batch age
=====================================  ================================

Estimators are hot-swappable: the coalescer resolves the registry's
*active* version at every batch, so ``promote``/``rollback`` take
effect on the next tick with zero downtime and zero dropped requests.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from ..analysis import tsan as _tsan
from ..analysis.protocols import (
    ACTOR_REPLICA, REPLICA_DRAIN, REPLICA_READY, REPLICA_STOP, REPLICA_WARM,
)
from ..resilience.errors import OverloadedError
from ..resilience.faults import inject as _inject
from ..telemetry import alerts as _alerts
from ..telemetry import journal as _journal
from ..telemetry import metrics as _tm
from ..telemetry import server as _tserver
from ..telemetry import sketch as _sketch
from ..telemetry import slo as _slo
from ..telemetry import tracing as _tracing
from ..telemetry.spans import stage_note as _stage_note
from . import canary as _canary
from .admission import QOS_CLASSES, AdmissionController
from .coalescer import ModelBatcher, observe_stage
from .model_io import infer as _infer
from .registry import ModelRegistry

__all__ = [
    "InferenceService",
    "default_service",
    "start_serving",
    "stop_serving",
]

#: lifecycle journal action per target state (PROTOCOLS "replica")
_STATE_ACTIONS = {
    "warming": REPLICA_WARM,
    "ready": REPLICA_READY,
    "draining": REPLICA_DRAIN,
    "stopped": REPLICA_STOP,
}

#: per-process instance counter behind each service's replica key
_SERVICE_SEQ = itertools.count()

_LATENCY_H = _tm.histogram(
    "serving.latency_ms", "end-to-end predict latency (admission to result)"
)

#: route prefix the service mounts on the introspection server
ROUTE_PREFIX = "/v1/"


def _env():
    from ..core import _env as envmod

    return envmod


class InferenceService:
    """A running inference service over a :class:`ModelRegistry`.

    ``split`` is the batch axis distribution of coalesced batches:
    ``None`` (default) replicates the bucket-padded batch — the right
    call at online batch sizes, and the path whose every op rides the
    executable cache; ``0`` shards rows across the serving mesh for
    large-bucket deployments (its predict programs are the jitted ring
    kernels, cached per bucket by jax itself).  Knobs default from the
    registry (``HEAT_TPU_SERVE_*``); constructor arguments override per
    instance."""

    def __init__(
        self,
        registry: Optional[ModelRegistry] = None,
        comm=None,
        split: Optional[int] = None,
        max_batch: Optional[int] = None,
        max_delay_ms: Optional[float] = None,
        queue_depth: Optional[int] = None,
        rate: Optional[float] = None,
        burst: Optional[float] = None,
    ):
        env = _env()
        self.registry = registry if registry is not None else ModelRegistry(comm=comm)
        self.split = split
        self.max_batch = (
            int(max_batch) if max_batch is not None
            else env.env_int("HEAT_TPU_SERVE_MAX_BATCH")
        )
        delay_ms = (
            float(max_delay_ms) if max_delay_ms is not None
            else env.env_float("HEAT_TPU_SERVE_MAX_DELAY_MS")
        )
        self.max_delay_s = delay_ms / 1e3
        self.admission = AdmissionController(
            max_depth=(
                int(queue_depth) if queue_depth is not None
                else env.env_int("HEAT_TPU_SERVE_QUEUE_DEPTH")
            ),
            default_rate=(
                float(rate) if rate is not None
                else env.env_float("HEAT_TPU_SERVE_RATE")
            ),
            default_burst=(
                float(burst) if burst is not None
                else env.env_float("HEAT_TPU_SERVE_BURST")
            ),
        )
        self._batchers: Dict[str, ModelBatcher] = {}
        self._open = True
        self._started_monitor = False
        #: per-tenant cost metering (HEAT_TPU_QOS_METER): each coalesced
        #: batch's analyzed FLOPs/bytes + device-ms are attributed to
        #: its member tenants pro rata by rows (/tenantz)
        self._meter = env.env_flag("HEAT_TPU_QOS_METER")
        #: batcher-thread-local handoff from _infer_batch (which meters
        #: the inference) to _account_batch (which settles it) — both
        #: run on the same batcher thread, in that order, per batch
        self._infer_cost = threading.local()
        #: lifecycle state the /readyz readiness verdict keys off:
        #: "warming" (up, pre-warming the executable cache — not ready),
        #: "ready" (routable), "draining" (finishing in-flight work —
        #: not ready), "stopped" (terminal, post-close).  Liveness
        #: (/healthz) is unaffected by any of it.  The machine is
        #: declared in analysis/protocols.py ("replica"); every change
        #: goes through :meth:`set_state`, which journals it.
        self._state = "ready"
        #: stable per-instance key the lifecycle journal events carry
        #: (the conformance checker tracks one machine per replica)
        self._replica_key = f"pid{os.getpid()}-svc{next(_SERVICE_SEQ)}"
        #: (model, bucket_rows, features, dtype) per coalesced-batch
        #: shape this service has dispatched — the pre-warm manifest a
        #: fresh replica replays to reach hit rate 1.0 before its first
        #: request (export_prewarm_manifest/prewarm)
        self._seen_shapes: set = set()
        self._lock = _tsan.register_lock("serving.service")
        #: the canary decision plane: shadow-mirrors a fraction of every
        #: coalesced batch to the loaded canary version (registry
        #: ``load(activate=False)``), compares online, auto-promotes /
        #: auto-rolls-back — see serving/canary.py and /canaryz
        self.canary = _canary.CanaryController(self)
        # tenant metering (/tenantz, telemetry/tenants.py) bills each
        # batch by the analyzed FLOPs/bytes of what it dispatched, and
        # those exist only where a compile recorded its cost.  Serving
        # compiles are bounded (one per (model, bucket)), so the
        # per-miss accounting is a warmup-only tax.
        from ..core import dispatch as _dispatch

        _dispatch.set_cost_accounting(True)

    # -- model lifecycle (thin registry delegates) ----------------------
    def load(self, name: str, directory: str, **kwargs) -> int:
        """Hot-load a model version (see :meth:`ModelRegistry.load`)."""
        return self.registry.load(name, directory, **kwargs)

    def load_async(self, name: str, directory: str, **kwargs):
        """Background hot-load (see :meth:`ModelRegistry.load_async`)."""
        return self.registry.load_async(name, directory, **kwargs)

    def set_quota(self, tenant: str, rate: float, burst: Optional[float] = None) -> None:
        self.admission.set_quota(tenant, rate, burst)

    def set_class(self, tenant: str, cls: str) -> None:
        """Pin ``tenant``'s QoS class (``latency``/``standard``/``batch``,
        docs/serving.md "QoS scheduling")."""
        self.admission.set_class(tenant, cls)

    # -- the hot path ---------------------------------------------------
    def _batcher(self, name: str) -> ModelBatcher:
        self.registry.record(name)  # KeyError -> 404 before a thread spawns
        with self._lock:
            _tsan.note_access("serving.service.state")
            if not self._open:
                raise RuntimeError("inference service is closed")
            b = self._batchers.get(name)
            if b is None:
                b = self._batchers[name] = ModelBatcher(
                    name,
                    lambda rows, _n=name: self._infer_batch(_n, rows),
                    max_batch=self.max_batch,
                    max_delay_s=self.max_delay_s,
                    # drift sketches fold each batch's TRUE rows in
                    # after the callers are woken (HEAT_TPU_SKETCH)
                    on_batch=lambda rows, _n=name: _sketch.record_batch(_n, rows),
                    # shadow mirroring to the loaded canary version —
                    # sampling + a bounded enqueue only; the canary
                    # inference runs on the controller's shadow thread
                    on_mirror=lambda rows, out, tid, ms, _n=name: (
                        self.canary.offer(_n, rows, out, tid, ms)
                    ),
                    # per-tenant cost settlement (HEAT_TPU_QOS_METER) —
                    # reads the metered inference cost _infer_batch
                    # parked on this same batcher thread
                    on_account=(
                        (lambda parts, ms, _n=name: self._account_batch(_n, parts, ms))
                        if self._meter
                        else None
                    ),
                )
            return b

    def _infer_batch(self, name: str, rows: np.ndarray) -> np.ndarray:
        """One coalesced inference on the ACTIVE version (batcher thread,
        under the primary request's trace context).  Decomposed into the
        ``dispatch`` stage (DNDarray wrap + program dispatch — any
        compile span nests here and inherits the trace) and the
        ``execute`` stage (forcing the result: device compute + fetch)."""
        from contextlib import nullcontext

        from ..core import dispatch as _dispatch
        from ..core import factories

        est = self.registry.get(name)
        with self._lock:
            _tsan.note_access("serving.service.state")
            self._seen_shapes.add(
                (name, int(rows.shape[0]), int(rows.shape[1]), str(rows.dtype))
            )
        tid = _tracing.current_trace_id()
        td0 = time.perf_counter_ns()
        # cost metering scope: every dispatch of this batch's inference
        # adds its analyzed FLOPs/bytes to the meter; _account_batch
        # (same batcher thread, right after the callers wake) splits it
        # across the batch's tenants
        with (_dispatch.meter_costs() if self._meter else nullcontext(None)) as meter:
            t0 = time.perf_counter_ns()
            # the ambient trace context is live here, so a cold bucket's
            # dispatch.compile span inherits the request that paid for it
            x = factories.array(rows, split=self.split, comm=self.registry.comm)
            y = _infer(est, x)
            t1 = time.perf_counter_ns()
            _stage_note("serve.dispatch", t0, t1 - t0, model=name, rows=int(rows.shape[0]))
            observe_stage("dispatch", (t1 - t0) / 1e6, tid)
            t0 = time.perf_counter_ns()
            out = y.numpy()
            t1 = time.perf_counter_ns()
            _stage_note("serve.execute", t0, t1 - t0, model=name)
            observe_stage("execute", (t1 - t0) / 1e6, tid)
        if meter is not None:
            self._infer_cost.last = (
                meter.flops,
                meter.bytes_accessed,
                (time.perf_counter_ns() - td0) / 1e6,
            )
        return out

    def _account_batch(self, name: str, parts, infer_ms: float) -> None:
        """Settle one coalesced batch into the tenant ledger (/tenantz):
        the metered cost _infer_batch parked on this thread, split pro
        rata by rows.  Batcher-thread hook — never a caller's latency."""
        from ..telemetry import tenants as _tenants

        cost = getattr(self._infer_cost, "last", None)
        self._infer_cost.last = None
        flops, bytes_accessed, device_ms = cost if cost else (0.0, 0.0, float(infer_ms))
        _tenants.note_batch(
            name, parts, flops=flops, bytes_accessed=bytes_accessed,
            device_ms=device_ms,
        )

    def predict(
        self,
        name: str,
        rows,
        tenant: str = "default",
        timeout: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> np.ndarray:
        """Predict ``rows`` (one (n, features) request) on model
        ``name``; blocks until the coalesced batch answers.

        ``deadline_s`` is an explicit coalescing deadline budget
        (seconds from now; default: the tenant's class budget,
        ``HEAT_TPU_QOS_DEADLINE_*_MS``).  Raises
        :class:`OverloadedError` when shed, ``KeyError`` for an unknown
        model, the batch's error when its dispatch failed."""
        out, _info = self._predict(
            name, rows, tenant=tenant, timeout=timeout, deadline_s=deadline_s
        )
        return out

    def _predict(
        self,
        name: str,
        rows,
        tenant: str = "default",
        timeout: Optional[float] = None,
        trace_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ):
        """The traced predict path: returns ``(out, info)`` where
        ``info`` carries the request's ``trace_id`` and its measured
        ``latency_ms`` — the ONE timing source both the
        ``serving.latency_ms`` histogram and the HTTP response report
        (the route must never re-time the request independently).

        ``trace_id`` adopts an inbound id (the fleet router stamps its
        own into the forwarded body), so one routed request's spans
        stitch across router and replica by the existing trace_id
        merge."""
        rows = np.asarray(rows)
        if rows.ndim == 1:
            rows = rows[None, :]
        _inject("serve.predict", model=name, rows=int(rows.shape[0]))
        n = int(rows.shape[0])
        req = _tracing.request_span(
            f"/v1/predict/{name}", trace_id=trace_id, model=name, tenant=tenant, rows=n
        )
        with req:
            t0 = time.perf_counter_ns()
            try:
                cls = self.admission.admit(tenant, n)
            finally:
                t1 = time.perf_counter_ns()
                _stage_note(
                    "serve.admission", t0, t1 - t0, tenant=tenant, rows=n
                )
            observe_stage("admission", (t1 - t0) / 1e6, req.trace_id)
            try:
                out = self._batcher(name).submit(
                    rows, timeout=timeout, tenant=tenant, cls=cls,
                    deadline_s=deadline_s,
                )
            finally:
                self.admission.release(n, cls)
        _LATENCY_H.observe(
            req.duration_ms,
            exemplar=req.trace_id
            if (req.trace_id and _tracing.exemplars_enabled())
            else None,
        )
        return out, {"trace_id": req.trace_id, "latency_ms": req.duration_ms}

    # -- lifecycle state + readiness ------------------------------------
    _STATES = ("warming", "ready", "draining", "stopped")

    @property
    def state(self) -> str:
        """Lifecycle state: "warming" / "ready" / "draining" /
        "stopped"."""
        with self._lock:
            _tsan.note_access("serving.service.state", write=False)
            return self._state

    def set_state(self, state: str) -> str:
        """Set the lifecycle state (readiness flips with it); returns
        the previous state.  The registered transition helper of the
        ``replica`` protocol: every actual change is journaled (actor
        ``replica``) after the lock is released, keyed by this
        instance's replica key."""
        if state not in self._STATES:
            raise ValueError(
                f"unknown service state {state!r}; expected one of {self._STATES}"
            )
        with self._lock:
            _tsan.note_access("serving.service.state")
            prev, self._state = self._state, state
        if prev != state:
            _journal.emit(
                ACTOR_REPLICA, _STATE_ACTIONS[state],
                severity="info",
                message=f"replica lifecycle: {prev} -> {state}",
                evidence={"replica": self._replica_key, "prev": prev},
            )
        return prev

    def readiness(self):
        """``(ready, doc)`` for the introspection server's ``/readyz``:
        ready iff the service is in state "ready".  The doc carries the
        state, the loaded model names (the router's placement map), the
        queue/in-flight picture, and the dispatch-cache counters at
        scrape time (the cold-start gate reads the miss count at
        ready-time from here)."""
        from ..core import aot_cache as _aot
        from ..core import dispatch as _dispatch

        with self._lock:
            _tsan.note_access("serving.service.state", write=False)
            state = self._state
            batchers = list(self._batchers.values())
        stats = _dispatch.cache_stats()
        doc: Dict[str, Any] = {
            "ready": state == "ready",
            "state": state,
            "models": self.registry.model_names(),
            "queued_rows": sum(b.queued_rows() for b in batchers),
            "admitted_rows_in_flight": self.admission.depth(),
            "dispatch": {
                "misses": stats["misses"],
                "hits": stats["hits"],
                "hit_rate": stats["hit_rate"],
            },
            "aot": {
                k: v for k, v in _aot.stats().items() if k in ("hits", "saves", "errors")
            },
        }
        return doc["ready"], doc

    # -- pre-warm manifest ----------------------------------------------
    def export_prewarm_manifest(self, path: Optional[str] = None) -> Dict[str, Any]:
        """The (model, bucket, features, dtype) shapes this live service
        has dispatched, as a manifest document a fresh replica replays
        before taking traffic.  ``path`` writes it atomically with a
        CRC32 sidecar like every other artifact."""
        with self._lock:
            _tsan.note_access("serving.service.state", write=False)
            shapes = sorted(self._seen_shapes)
        doc = {
            "version": 1,
            "exported_at": time.time(),
            "entries": [
                {"model": m, "bucket": b, "features": f, "dtype": dt}
                for (m, b, f, dt) in shapes
            ],
        }
        if path is not None:
            from ..resilience.atomic import atomic_write

            with atomic_write(path, fault_site="io.write") as tmp:
                with open(tmp, "w") as fh:
                    json.dump(doc, fh, indent=1, sort_keys=True)
        return doc

    @staticmethod
    def load_prewarm_manifest(path: str) -> Dict[str, Any]:
        """Read (and checksum-verify) a manifest written by
        :meth:`export_prewarm_manifest`."""
        from ..resilience.atomic import verify_checksum

        verify_checksum(path)
        with open(path) as fh:
            return json.load(fh)

    def prewarm(
        self,
        manifest: Optional[Dict[str, Any]] = None,
        path: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Drive one synthetic coalesced batch per manifest entry so
        every (model, bucket) executable is resident — loaded from the
        AOT cache when armed, compiled otherwise — BEFORE the first real
        request.  Entries naming models this service has not loaded are
        skipped (counted).  Returns ``{"warmed", "skipped",
        "new_compiles", "aot_hits"}`` where ``new_compiles`` is actual
        compiles (in-memory misses minus AOT artifact loads): with a
        populated AOT cache it is 0 — the cold-start elimination the
        fleet gate enforces."""
        from ..core import aot_cache as _aot
        from ..core import dispatch as _dispatch

        if manifest is None:
            if path is None:
                raise ValueError("prewarm needs a manifest document or a path")
            manifest = self.load_prewarm_manifest(path)
        s0 = _dispatch.cache_stats()
        a0 = _aot.stats()
        warmed = skipped = 0
        for entry in manifest.get("entries", ()):
            name = str(entry["model"])
            try:
                self.registry.record(name)
            except KeyError:
                skipped += 1
                continue
            rows = np.zeros(
                (int(entry["bucket"]), int(entry["features"])),
                dtype=np.dtype(str(entry.get("dtype", "float32"))),
            )
            self._batcher(name)  # the batcher thread exists before traffic
            self._infer_batch(name, rows)  # the exact coalesced-batch program
            warmed += 1
        s1 = _dispatch.cache_stats()
        a1 = _aot.stats()
        aot_hits = a1["hits"] - a0["hits"]
        return {
            "warmed": warmed,
            "skipped": skipped,
            "new_compiles": (s1["misses"] - s0["misses"]) - aot_hits,
            "aot_hits": aot_hits,
        }

    # -- graceful drain -------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: flip to "draining" (readiness goes 503 so
        a router stops sending new work), keep serving until every
        admitted row is answered and every queue is empty (bounded by
        ``timeout``, default ``HEAT_TPU_FLEET_DRAIN_TIMEOUT_S``), then
        :meth:`close`.  Returns True when the drain completed with zero
        abandoned requests.  The SIGTERM path of a fleet replica."""
        if timeout is None:
            timeout = _env().env_float("HEAT_TPU_FLEET_DRAIN_TIMEOUT_S")
        self.set_state("draining")
        deadline = time.monotonic() + max(float(timeout), 0.0)
        drained = False
        while True:
            with self._lock:
                _tsan.note_access("serving.service.state", write=False)
                batchers = list(self._batchers.values())
            if self.admission.depth() == 0 and all(
                b.queued_rows() == 0 for b in batchers
            ):
                drained = True
                break
            if time.monotonic() >= deadline:
                break
            time.sleep(0.02)
        self.close()
        return drained

    # -- per-model health ----------------------------------------------
    def model_health(self, name: str) -> Dict[str, Any]:
        """``(healthy, doc)`` folded into one doc with a ``healthy``
        key: loaded version, batcher liveness, queue depth, last-batch
        timestamp + trace_id — enough for an operator to tell "idle"
        (no queue, old batch) from "stuck" (deep queue, old batch) and
        to jump from a stuck model straight to its last served trace in
        ``/tracez``, without scraping ``/varz``."""
        rec = self.registry.record(name)  # KeyError -> 404 upstream
        with self._lock:
            _tsan.note_access("serving.service.state", write=False)
            b = self._batchers.get(name)
        now = time.time()
        doc: Dict[str, Any] = {
            "model": name,
            "status": "ok",
            "healthy": True,
            "version": rec["version"],
            "kind": rec["kind"],
            "loaded_age_s": round(now - rec["loaded_at"], 3),
            "world_size_written": rec["world_size_written"],
            "world_size_serving": rec["world_size_serving"],
            "queued_rows": b.queued_rows() if b is not None else 0,
            "admitted_rows_in_flight": self.admission.depth(),
            "last_batch_ts": (
                b.last_batch_ts if b is not None and b.last_batch_ts > 0 else None
            ),
            "last_batch_age_s": (
                round(now - b.last_batch_ts, 3)
                if b is not None and b.last_batch_ts > 0
                else None
            ),
            "last_batch_trace_id": b.last_batch_trace_id if b is not None else None,
        }
        # per-lane picture: queued rows + oldest-waiting-age from this
        # model's coalescer joined with the service-wide admission lane
        # depths/limits — "latency stuck behind batch" is diagnosable
        # from this route alone, no /varz scrape needed
        queue_lanes = (
            b.lane_depths()
            if b is not None
            else {c: {"queued_rows": 0, "oldest_wait_s": 0.0} for c in QOS_CLASSES}
        )
        adm_lanes = self.admission.lane_depths()
        doc["lanes"] = {
            c: {
                "queued_rows": queue_lanes[c]["queued_rows"],
                "oldest_wait_s": queue_lanes[c]["oldest_wait_s"],
                "admitted_rows_in_flight": adm_lanes[c]["depth"],
                "depth_limit": adm_lanes[c]["limit"],
            }
            for c in QOS_CLASSES
        }
        if b is None:
            doc["status"] = "idle"  # loaded, no traffic yet — healthy
        elif not b.alive():
            doc["status"] = "dead"
            doc["healthy"] = False
        # lifecycle state rides along so "idle" (no traffic yet) and
        # "warming" (pre-warm still running) are distinguishable, and a
        # draining replica's models say so; liveness is unaffected —
        # readiness is /readyz's verdict, not this route's
        state = self.state
        doc["state"] = state
        if state != "ready" and doc["status"] in ("ok", "idle"):
            doc["status"] = state
        # quality signals: the model's drift score and any alert that
        # names it — liveness (healthy/503) is unaffected, but the
        # status string flips so a canary driver or operator sees a
        # drifting model without scraping /driftz
        drift = _sketch.SKETCHES.status(name)
        doc["drift"] = {
            "score": drift["score"],
            "drifting": drift["drifting"],
            "threshold": drift["threshold"],
            "baseline": drift["baseline"],
            "sketched_rows": drift["sketched_rows"],
        }
        doc["alerts"] = [
            a for a in _alerts.active_alerts()
            if a["labels"].get("model") == name or a["name"] == f"drift:{name}"
        ]
        if drift["drifting"] and doc["status"] in ("ok", "idle"):
            doc["status"] = "drifting"
        # canary state rides along so an operator sees "a canary is
        # under evaluation / its last verdict" without scraping /canaryz
        cstate = _canary.status(name)
        doc["canary_version"] = self.registry.canary_version(name)
        doc["shadow_sampled_rows"] = cstate["rows"] if cstate else 0
        doc["last_canary_verdict"] = (
            (cstate.get("decision") or {}).get("verdict") or cstate.get("verdict")
            if cstate else None
        )
        return doc

    def freeze_baseline(self, name: str) -> Dict[str, Any]:
        """Freeze the model's live input sketch as its drift baseline
        (runtime capture — e.g. right after warm-up traffic known to be
        in-distribution); returns the baseline document, which
        :func:`~heat_tpu.serving.model_io.save_model` can persist with
        the next version."""
        self.registry.record(name)  # KeyError -> 404 upstream
        return _sketch.SKETCHES.freeze_baseline(name)

    # -- HTTP -----------------------------------------------------------
    def serve(self, port: Optional[int] = None) -> str:
        """Mount the /v1 routes on the introspection server (starting it
        if needed), install the default serving SLOs, and start the
        burn-rate monitor tick (``HEAT_TPU_SLO_TICK_S``; unset/0 falls
        back to 1 s for a serving process — a fleet replica must page
        itself without configuration); returns the server URL."""
        srv = _tserver.start_server(port)
        _tserver.register_route(ROUTE_PREFIX, self._handle_http)
        # readiness (/readyz) now reflects THIS service's lifecycle
        # state — a fleet router keys routing off it (docs/fleet.md)
        _tserver.set_readiness(self.readiness)
        _slo.install_default_slos()
        tick = _env().env_float("HEAT_TPU_SLO_TICK_S")
        self._started_monitor = _slo.start_monitor(tick if tick > 0 else 1.0)
        return srv.url

    def _handle_http(self, method: str, path: str, body: Optional[bytes]):
        try:
            if method == "GET" and path == "/v1/models":
                return 200, "application/json", json.dumps(
                    {"models": self.registry.models()}, indent=1, default=str
                )
            if method == "GET" and path.startswith("/v1/models/") and path.endswith("/healthz"):
                name = path[len("/v1/models/") : -len("/healthz")].strip("/")
                doc = self.model_health(name)
                return (
                    200 if doc["healthy"] else 503,
                    "application/json",
                    json.dumps(doc, indent=1, default=str),
                )
            if method == "POST" and path == "/v1/predict":
                return self._handle_predict(body)
            return 404, "text/plain", f"unknown serving route {path!r}\n"
        except KeyError as e:
            return 404, "application/json", json.dumps({"error": str(e)})
        except OverloadedError as e:
            headers = {}
            if e.retry_after_s is not None:
                headers["Retry-After"] = f"{max(e.retry_after_s, 0.001):.3f}"
            return (
                429,
                "application/json",
                json.dumps(
                    {"error": str(e), "cause": e.cause, "tenant": e.tenant,
                     "retry_after_s": e.retry_after_s}
                ),
                headers,
            )
        except (ValueError, TypeError) as e:
            return 400, "application/json", json.dumps(
                {"error": f"{type(e).__name__}: {e}"}
            )

    def _handle_predict(self, body: Optional[bytes]):
        try:
            doc = json.loads(body or b"")
        except ValueError:
            return 400, "application/json", json.dumps(
                {"error": "request body must be a JSON object"}
            )
        if not isinstance(doc, dict) or "model" not in doc or "inputs" not in doc:
            return 400, "application/json", json.dumps(
                {"error": 'POST /v1/predict needs {"model": name, "inputs": [[...], ...]}'}
            )
        name = doc["model"]
        rows = np.asarray(doc["inputs"], dtype=np.float32)
        tenant = str(doc.get("tenant", "default"))
        # explicit coalescing deadline: the ``deadline_ms`` body field
        # wins over the ``X-Heat-Deadline-Ms`` header (the body rides
        # through the fleet router's proxy verbatim; the header works at
        # the replica surface)
        deadline_ms = doc.get("deadline_ms")
        if deadline_ms is None:
            deadline_ms = _tserver.request_headers().get("x-heat-deadline-ms")
        try:
            deadline_s = float(deadline_ms) / 1e3 if deadline_ms is not None else None
        except (TypeError, ValueError):
            return 400, "application/json", json.dumps(
                {"error": f"deadline_ms must be a number, got {deadline_ms!r}"}
            )
        # one timing source: the latency (and trace id) the response
        # reports IS the measurement serving.latency_ms observed — the
        # route never re-times the request independently
        trace_id = doc.get("trace_id")
        out, info = self._predict(
            name, rows, tenant=tenant, timeout=doc.get("timeout"),
            trace_id=str(trace_id) if trace_id else None,
            deadline_s=deadline_s,
        )
        version = self.registry.active_version(name)
        return 200, "application/json", json.dumps(
            {
                "model": name,
                "version": version,
                "n": int(np.asarray(out).shape[0]),
                "predictions": np.asarray(out).tolist(),
                "latency_ms": round(info["latency_ms"], 3),
                "trace_id": info["trace_id"],
            }
        )

    # -- shutdown -------------------------------------------------------
    def close(self) -> None:
        """Unmount the routes, drain and join every batcher, drain the
        registry's background loader.  Idempotent."""
        self.set_state("stopped")  # terminal lifecycle transition (journaled once)
        _tserver.unregister_route(ROUTE_PREFIX)
        _tserver.clear_readiness(self.readiness)
        if self._started_monitor:
            self._started_monitor = False
            _slo.stop_monitor()
        with self._lock:
            _tsan.note_access("serving.service.state")
            self._open = False
            batchers, self._batchers = dict(self._batchers), {}
        for b in batchers.values():
            b.close()
        self.canary.close()
        self.registry.close()

    def __enter__(self) -> "InferenceService":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


# ----------------------------------------------------------------------
# process-default service (the HTTP deployment shape: one process, one
# registry, one port)
# ----------------------------------------------------------------------
_SERVICE: Optional[InferenceService] = None
_SERVICE_LOCK = _tsan.register_lock("serving.service")


def default_service(**kwargs) -> InferenceService:
    """Get-or-create the process's default :class:`InferenceService`
    (kwargs apply only on creation)."""
    global _SERVICE
    with _SERVICE_LOCK:
        _tsan.note_access("serving.service.state")
        if _SERVICE is None:
            _SERVICE = InferenceService(**kwargs)
        return _SERVICE


def start_serving(port: Optional[int] = None, **kwargs) -> InferenceService:
    """Start the default service and mount its HTTP routes; returns the
    service (its URL comes from ``telemetry.server``)."""
    svc = default_service(**kwargs)
    svc.serve(port)
    return svc


def stop_serving() -> None:
    """Close and drop the default service (no-op when none is running)."""
    global _SERVICE
    with _SERVICE_LOCK:
        _tsan.note_access("serving.service.state")
        svc, _SERVICE = _SERVICE, None
    if svc is not None:
        svc.close()
