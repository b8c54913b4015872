"""Central lock registry: the concurrency analogue of ``KNOBS``/``KNOWN_SITES``.

The reference design is single-threaded per rank (one MPI process runs
one program over one local chunk — PAPER.md), but this framework has
grown real thread surface: the async-checkpoint writer
(``utils/overlap.py``), prefetch loader threads
(``utils/data/partial_dataset.py``), the introspection HTTP server and
crash excepthooks (``telemetry/``), and the fault injector evaluated
from any of them.  Every lock that guards cross-thread state is declared
ONCE in the :data:`LOCK_REGISTRY` table below — name, owning file, the
lexical spelling(s) a ``with`` statement uses to hold it, the shared
structures it guards, and a one-line doc.  Three consumers share the
table:

* the AST linter's **H7xx** rules (``heat_tpu/analysis/ast_lint.py``)
  statically parse it (``ast.literal_eval``, no imports) — H701 flags a
  module-global mutated from thread-reachable code outside a registered
  lock's ``with`` block, H704 flags blocking calls lexically inside one;
* the runtime sanitizer (:mod:`heat_tpu.analysis.tsan`) wraps every
  registered lock in an instrumented proxy when ``HEAT_TPU_TSAN=1`` —
  recording per-thread acquisition stacks, the global lock-order graph
  (cycle = potential deadlock), and off-thread access to the registered
  structures without their lock;
* ``docs/static_analysis.md`` documents the workflow: a new lock that
  guards cross-thread state must be registered here (and created via
  ``tsan.register_lock``) before it can merge.

The table is a **pure literal** so the linter can read it without
importing jax or the modules it describes.
"""

from __future__ import annotations

from typing import Dict, Set, Tuple

__all__ = [
    "LOCK_REGISTRY",
    "lock_for_structure",
    "registered_lock_names",
    "registered_spellings",
    "registered_structures",
]

#: Every registered cross-thread lock: name -> {file, spellings,
#: structures, doc}.  ``file`` is the repo-relative module that creates
#: the lock; ``spellings`` are the lexical forms a ``with`` statement
#: holding it uses in that module (what the H701/H704 rules match);
#: ``structures`` are the shared-state names the lock guards (what
#: ``tsan.note_access`` checkpoints reference).  PURE LITERAL — the AST
#: linter parses this assignment statically (ast.literal_eval).
LOCK_REGISTRY = {
    "telemetry.metrics.registry": {
        "file": "heat_tpu/telemetry/metrics.py",
        "spellings": ("self._lock",),
        "structures": ("telemetry.metrics.registry",),
        "doc": "MetricsRegistry._metrics name->metric map (get-or-make, snapshot, reset, Prometheus expose); per-metric value locks stay unregistered leaf locks",
    },
    "telemetry.spans.ring": {
        "file": "heat_tpu/telemetry/spans.py",
        "spellings": ("_RING_LOCK",),
        "structures": ("telemetry.spans.ring",),
        "doc": "the bounded span ring buffer: appended by span() from any thread, iterated by get_spans/chrome_trace_doc (the /trace route runs on an HTTP handler thread)",
    },
    "telemetry.tracing.store": {
        "file": "heat_tpu/telemetry/tracing.py",
        "spellings": ("_STORE_LOCK",),
        "structures": ("telemetry.tracing.store",),
        "doc": "the tail-sampled trace store: in-flight trace table mutations (begin/finish on request threads) and the recent/slowest/error retention structures (snapshots from /tracez handler threads and the crash excepthook); per-trace span lists are unregistered leaf structures appended lock-free (GIL-atomic list.append, dict read-only) on the serving hot path — like the per-metric value locks",
    },
    "telemetry.server": {
        "file": "heat_tpu/telemetry/server.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.server.singleton", "telemetry.server.routes", "telemetry.server.readiness"),
        "doc": "the process's single IntrospectionServer handle (start_server/stop_server swap it), the registered extra-route map (register_route/unregister_route mutate, handler threads take it briefly for the prefix lookup and call the handler outside it), and the readiness-provider slot /readyz consults",
    },
    "telemetry.flight_recorder.hooks": {
        "file": "heat_tpu/telemetry/flight_recorder.py",
        "spellings": ("_LOCK",),
        "structures": (),
        "doc": "install/uninstall state of the sys/threading excepthooks (_DIR and the saved previous hooks)",
    },
    "telemetry.flight_recorder.dump": {
        "file": "heat_tpu/telemetry/flight_recorder.py",
        "spellings": ("_DUMP_LOCK",),
        "structures": ("telemetry.flight_recorder.state",),
        "doc": "serializes crash-bundle writes: two threads crashing concurrently write one bundle each (distinct thread-id suffixes) instead of racing on one path; guards _LAST_PATH",
    },
    "telemetry.alerts": {
        "file": "heat_tpu/telemetry/alerts.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.alerts.state",),
        "doc": "the alert active table + fired/resolved transition ring: SLO monitors fire from the tick thread, drift checks from batcher threads, /sloz + /statusz handler threads read",
    },
    "telemetry.journal": {
        "file": "heat_tpu/telemetry/journal.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.journal.state",),
        "doc": "the decision-journal hot ring + durable-segment cursor: every autonomous controller emits from its own thread (SLO tick, shadow thread, router poller, fit threads), /decisionz handler threads and snapshot gathers read; the durable segment append runs under it too (control-plane rates, the streaming segment-log trade)",
    },
    "telemetry.tsdb": {
        "file": "heat_tpu/telemetry/tsdb.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.tsdb.state",),
        "doc": "the metric-history ring map + sampler-thread handle: the sampler scrapes on its interval, controllers push via record(), /queryz handler threads read; the registry scrape itself runs outside it (no cross-module lock nesting)",
    },
    "telemetry.slo": {
        "file": "heat_tpu/telemetry/slo.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.slo.state",),
        "doc": "the registered-SLO table, per-SLO cumulative sample rings, cached /sloz report, and the tick-thread handle: the evaluation tick mutates while /sloz handler threads render; alert transitions run OUTSIDE this lock (alerts has its own)",
    },
    "telemetry.sketch": {
        "file": "heat_tpu/telemetry/sketch.py",
        "spellings": ("self._lock",),
        "structures": ("telemetry.sketch.registry",),
        "doc": "SketchRegistry model->(live sketch, baseline) table: batcher threads fold coalesced batches in, freeze/set_baseline swaps documents, /driftz + per-model /healthz handler threads score",
    },
    "analysis.program_lint.keys": {
        "file": "heat_tpu/analysis/program_lint.py",
        "spellings": ("_KEY_LOCK",),
        "structures": ("analysis.program_lint.key_groups",),
        "doc": "normalized-dispatch-key groups the J103 recompile-churn check accumulates; misses can compile on any thread that dispatches",
    },
    "analysis.memory_model.estimates": {
        "file": "heat_tpu/analysis/memory_model.py",
        "spellings": ("_EST_LOCK",),
        "structures": ("analysis.memory_model.estimates",),
        "doc": "the bounded per-program peak-HBM estimate table: written by note_estimate() on whichever thread triggered the dispatch compile, read by /statusz handler threads and the crash excepthook",
    },
    "analysis.diagnostics.ring": {
        "file": "heat_tpu/analysis/diagnostics.py",
        "spellings": ("_LOCK",),
        "structures": ("analysis.diagnostics.ring",),
        "doc": "the bounded recent-diagnostics ring: emit() appends from any thread (program lint on the dispatch path, tsan findings), recent_diagnostics() lists",
    },
    "analysis.conformance": {
        "file": "heat_tpu/analysis/conformance.py",
        "spellings": ("_LOCK",),
        "structures": ("analysis.conformance.state",),
        "doc": "the protocol-conformance tracked machine states + bounded recent-violations list: note_emit() steps from whichever thread journaled (a strict leaf — journal.emit calls it only after the telemetry.journal lock is released; the violation alert/diagnostic is reported outside it)",
    },
    "resilience.faults.injector": {
        "file": "heat_tpu/resilience/faults.py",
        "spellings": ("self._lock",),
        "structures": ("resilience.faults.counters",),
        "doc": "FaultInjector per-site call indices + injected lists: sites are evaluated from the async-writer and loader threads; the lock keeps per-site call order deterministic",
    },
    "overlap.async_writer": {
        "file": "heat_tpu/utils/overlap.py",
        "spellings": ("self._error_lock",),
        "structures": ("overlap.async_writer.state",),
        "doc": "AsyncCheckpointer pending-error slot: written by the background writer thread, swapped out by save()/wait()/close() on the fit thread",
    },
    "dispatch.cache": {
        "file": "heat_tpu/core/dispatch.py",
        "spellings": ("_CACHE_LOCK",),
        "structures": ("dispatch.cache",),
        "doc": "the compiled-executable LRU + cost records: mutated per dispatch on the fit thread, iterated by cache_keys()/cost_summary() from HTTP handler threads (/statusz) and the crash excepthook",
    },
    "data.partial_loader": {
        "file": "heat_tpu/utils/data/partial_dataset.py",
        "spellings": ("self._lifecycle",),
        "structures": ("data.partial_loader.state",),
        "doc": "PartialH5DataLoaderIter worker-thread handle: close() is reachable from the consumer, __del__ (any thread via GC) and error paths concurrently",
    },
    "serving.registry": {
        "file": "heat_tpu/serving/registry.py",
        "spellings": ("self._lock",),
        "structures": ("serving.registry.models",),
        "doc": "ModelRegistry name->versions table + active pointers + loader error slot: mutated by (possibly background) loads and promote/rollback, read per batch by the coalescer thread and per request by HTTP handler threads",
    },
    "serving.coalescer": {
        "file": "heat_tpu/serving/coalescer.py",
        "spellings": ("self._cond", "self._lock"),
        "structures": ("serving.coalescer.queue",),
        "doc": "ModelBatcher request queue + open flag: request threads append under the Condition, the batcher thread drains per tick; the inference dispatch itself always runs outside the lock",
    },
    "serving.admission": {
        "file": "heat_tpu/serving/admission.py",
        "spellings": ("self._lock",),
        "structures": ("serving.admission.buckets",),
        "doc": "AdmissionController per-tenant token buckets + in-flight row count: admit/release fire on every request thread",
    },
    "serving.canary": {
        "file": "heat_tpu/serving/canary.py",
        "spellings": ("_LOCK", "self._cond", "self._lock"),
        "structures": ("serving.canary.state",),
        "doc": "the canary decision plane's per-model evidence windows + retained event ring + every controller's bounded shadow queue (ONE module lock instance): batcher threads offer mirrored batches, the shadow thread compares and decides, /canaryz + /statusz handler threads and the crash excepthook read; the canary inference itself always runs outside it",
    },
    "serving.service": {
        "file": "heat_tpu/serving/service.py",
        "spellings": ("self._lock", "_SERVICE_LOCK"),
        "structures": ("serving.service.state",),
        "doc": "InferenceService per-model batcher map, lifecycle state (warming/ready/draining), the pre-warm shape ledger + the module's default-service singleton: batchers are created lazily on first request (any handler thread), closed by close()",
    },
    "dispatch.aot": {
        "file": "heat_tpu/core/aot_cache.py",
        "spellings": ("_LOCK",),
        "structures": ("dispatch.aot.state",),
        "doc": "AOT-cache module configuration (armed directory, save flag, fingerprint memo): configure() swaps it while lookups fire from any dispatching thread (batchers, HTTP handlers); artifact files themselves need no lock — writes are atomic renames keyed per artifact",
    },
    "fleet.router": {
        "file": "heat_tpu/fleet/router.py",
        "spellings": ("self._lock",),
        "structures": ("fleet.router.replicas",),
        "doc": "FleetRouter replica table (readiness, model lists, in-flight counts, circuit-breaker states), the global admission bucket and the sliding latency window: mutated by request handler threads, the health poller and add/drain/remove; proxied HTTP calls always run outside it",
    },
    "fleet.replicas": {
        "file": "heat_tpu/fleet/replica.py",
        "spellings": ("self._lock",),
        "structures": ("fleet.replicas.table",),
        "doc": "LocalReplicaSet url->subprocess handle table: spawn/drain/stop run from the autoscaler tick thread and close() from the owner; Popen waits run outside the lock",
    },
    "fleet.autoscaler": {
        "file": "heat_tpu/fleet/autoscaler.py",
        "spellings": ("self._lock",),
        "structures": ("fleet.autoscaler.state",),
        "doc": "FleetAutoscaler hysteresis counters + last-decision record: mutated by the tick thread, read by /fleet/statusz handler threads and tests",
    },
    "streaming.segment_log": {
        "file": "heat_tpu/streaming/source.py",
        "spellings": ("self._lock",),
        "structures": ("streaming.segment_log.index",),
        "doc": "FileSegmentLog in-memory segment index (start offset -> file) + cached end offset: append() runs on producer threads (bench ingest, refresh drivers) while read()/size rescan from consumer threads; segment files themselves are immutable once atomically renamed in, so reads outside the lock see only committed bytes",
    },
    "core.preemption": {
        "file": "heat_tpu/core/preempt.py",
        "spellings": ("self._lock",),
        "structures": ("core.preemption.state",),
        "doc": "PreemptionGate pending-yield slot + counters: requested by admission/handler threads on a latency spike, consulted (and its stats mutated) by fit threads at resumable-fit chunk boundaries, cleared when the latency lane drains",
    },
    "telemetry.tenants": {
        "file": "heat_tpu/telemetry/tenants.py",
        "spellings": ("_LOCK",),
        "structures": ("telemetry.tenants.accounts",),
        "doc": "the per-tenant cost-metering account table (rows/FLOPs/bytes/device-ms per tenant): batcher threads settle each coalesced batch's pro-rata split in, /tenantz handler threads, the fleet poller scrape and the metrics dump read",
    },
    "streaming.refresh": {
        "file": "heat_tpu/streaming/refresh.py",
        "spellings": ("self._lock",),
        "structures": ("streaming.refresh.state",),
        "doc": "RefreshDriver lifecycle + last-refresh record (cooldown clock, saved versions, in-flight flag): check() fires from the poll thread or any caller, close() from the owner; the fit/save/load work itself always runs outside it",
    },
}


def registered_lock_names() -> Set[str]:
    """All registered lock names."""
    return set(LOCK_REGISTRY)


def registered_spellings() -> Set[str]:
    """Union of every registered lock's lexical ``with`` spellings (the
    set the H701/H704 lint rules match a ``with`` context against)."""
    out: Set[str] = set()
    for rec in LOCK_REGISTRY.values():
        out.update(rec["spellings"])
    return out


def registered_structures() -> Dict[str, str]:
    """structure name -> owning lock name, for every registered guarded
    structure (the table :func:`heat_tpu.analysis.tsan.note_access`
    checks against)."""
    out: Dict[str, str] = {}
    for lock_name, rec in LOCK_REGISTRY.items():
        for s in rec["structures"]:
            out[s] = lock_name
    return out


def lock_for_structure(name: str) -> str:
    """The registered owner lock of guarded structure ``name``."""
    try:
        return registered_structures()[name]
    except KeyError:
        raise KeyError(
            f"{name!r} is not a registered guarded structure; add it to a "
            "lock's 'structures' tuple in heat_tpu.analysis.concurrency."
            "LOCK_REGISTRY — the H7xx lint rules and the runtime sanitizer "
            "share that one table"
        ) from None
