"""Central environment-knob registry and shared env parsing.

Every ``HEAT_TPU_*`` tuning knob the framework reads is declared ONCE in
the :data:`KNOBS` table below — name, type, default, and a one-line doc.
The table is the machine-checked source of truth three consumers share:

* the typed accessors in this module (:func:`env_flag`, :func:`env_int`,
  :func:`env_float`, :func:`env_str`) refuse unregistered names, so a
  typo'd knob read fails loudly at import instead of silently returning
  its default forever;
* ``scripts/build_api_docs.py`` generates ``docs/env_vars.md`` from it,
  so the docs can never drift from the code;
* the AST linter's **H201** rule (``heat_tpu/analysis/ast_lint.py``)
  cross-checks every ``os.environ`` read of a ``HEAT_TPU_*`` literal in
  the sources against this table and flags unregistered names — new
  knobs must be registered here before they can merge.

The table is a **pure literal** (no computed values) so the linter can
read it with ``ast.literal_eval`` without importing jax.

Also hosts the shared precision tables the FFT and hsvd layers both
expose (``precision_from_env``).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax

__all__ = [
    "KNOBS",
    "env_flag",
    "env_float",
    "env_int",
    "env_str",
    "knob_default",
    "precision_from_env",
    "precision_name_from_env",
    "registered_knobs",
]

#: Every HEAT_TPU_* knob: name -> (type, default, doc).  ``type`` is one
#: of "bool" (0/false/no/off = off), "int", "float", "str", "path" or
#: "choice"; ``default`` is the value used when the variable is unset
#: (as a string, "" meaning "unset / auto-detect").  PURE LITERAL — the
#: AST linter parses this assignment statically (ast.literal_eval).
KNOBS = {
    # -- dispatch (core/dispatch.py, docs/dispatch.md) ------------------
    "HEAT_TPU_DISPATCH_CACHE": ("bool", "1", "executable cache under the generic op wrappers (0 = plain eager jnp calls, fusion off too)"),
    "HEAT_TPU_FUSION": ("bool", "1", "lazy elementwise chain fusion (0 = every op materializes immediately)"),
    "HEAT_TPU_FUSION_DEPTH": ("int", "16", "max pending-chain depth before a subchain is materialized"),
    "HEAT_TPU_DONATE": ("bool", "1", "refcount-proven buffer donation on in-place paths"),
    "HEAT_TPU_DISPATCH_CACHE_SIZE": ("int", "1024", "LRU capacity of the compiled-executable cache"),
    # -- static analysis (heat_tpu/analysis, docs/static_analysis.md) ---
    "HEAT_TPU_ANALYZE": ("choice", "0", "SPMD program analyzer on the dispatch compile path: 0 = off, 1 = warn, raise = error on any diagnostic"),
    "HEAT_TPU_ANALYZE_RING": ("int", "256", "capacity of the recent-diagnostics ring buffer"),
    "HEAT_TPU_TSAN": ("choice", "0", "concurrency sanitizer over the registered locks: 0 = off, 1 = armed (record tsan.* diagnostics), raise = armed + ProgramLintError at the finding site"),
    "HEAT_TPU_TSAN_DUMP": ("path", "", "write the sanitizer's findings as JSON to this path at process exit (the sanitized CI lane's audit artifact)"),
    "HEAT_TPU_TSAN_STACK_DEPTH": ("int", "10", "frames captured per lock-acquisition/access stack while the sanitizer is armed"),
    "HEAT_TPU_J202_THRESHOLD": ("int", "1024", "reduced-extent threshold of the J202 low-precision-accumulation rule: a bf16/f16 reduction or scan over this many elements or more without f32 accumulation is flagged"),
    "HEAT_TPU_HBM_BUDGET_BYTES": ("int", "0", "per-device HBM budget for the static peak-memory estimator: a freshly compiled program whose predicted per-device peak exceeds this many bytes emits J301 (0 = budget check off)"),
    "HEAT_TPU_PREDICT_DTYPE": ("choice", "", "low-precision predict compute dtype for tolerance-policy estimators (bfloat16; empty = native float32); kinds whose POLICIES entry is bitwise or does not list the dtype keep serving native and emit one J204"),
    "HEAT_TPU_PROTOCOL_CHECK": ("choice", "0", "runtime conformance of journal events against the declared control-plane protocols (analysis/protocols.py): 0 = off (one global read per emit), 1 = warn (H805 diagnostic + protocol:<actor> alert per illegal transition), raise = ProgramLintError at the offending emit site"),
    "HEAT_TPU_MODEL_CHECK_STATES": ("int", "200000", "bounded-model-checker state budget: the product state-space exploration of python -m heat_tpu.analysis.model_check aborts past this many distinct states"),
    # -- telemetry (heat_tpu/telemetry, docs/observability.md) ----------
    "HEAT_TPU_TRACE": ("bool", "1", "host-side span recording (0 = span() costs two attribute reads and records nothing)"),
    "HEAT_TPU_TRACE_RING": ("int", "4096", "span ring-buffer capacity (newest spans win)"),
    "HEAT_TPU_TRACE_KEEP": ("int", "32", "tail-sampled trace store: complete span trees retained per class (recent / slowest / shed+errored) after the span ring rotates (/tracez)"),
    "HEAT_TPU_TRACE_MAX_SPANS": ("int", "256", "span cap per retained trace in the tail store (extra spans are counted as dropped, never unbounded)"),
    "HEAT_TPU_TRACE_EXEMPLARS": ("bool", "1", "histogram exemplars: stage/latency histogram buckets remember the most recent trace_id that landed in them (OpenMetrics exemplar syntax on /metrics)"),
    "HEAT_TPU_METRICS_DUMP": ("path", "", "write the final metrics snapshot as JSON to this path at process exit"),
    "HEAT_TPU_HTTP_PORT": ("int", "0", "serve the runtime-introspection HTTP endpoint (/metrics /varz /healthz /trace /statusz) on this port (0 = off)"),
    "HEAT_TPU_HEALTH_MAX_AGE_S": ("float", "0", "/healthz flips unhealthy when the fit heartbeat is older than this many seconds (0 = staleness check off)"),
    "HEAT_TPU_FLIGHT_RECORDER": ("path", "", "crash flight recorder: write atomic crash bundles into this directory on unhandled exceptions (empty = off)"),
    "HEAT_TPU_COST_ANALYSIS": ("bool", "0", "record per-executable XLA cost/memory analysis at dispatch compile time (/statusz cost accounting)"),
    # -- quality signals: SLOs, drift, alerts (docs/observability.md) ---
    "HEAT_TPU_SLO_TICK_S": ("float", "0", "background SLO-monitor evaluation interval in seconds (0 = manual evaluate() only, except a serving process, which defaults its monitor to 1s when the /v1 routes mount)"),
    "HEAT_TPU_SLO_FAST_WINDOW_S": ("float", "60", "fast burn-rate window of the SLO monitors (page-latency window)"),
    "HEAT_TPU_SLO_SLOW_WINDOW_S": ("float", "300", "slow burn-rate window of the SLO monitors (flap suppressor)"),
    "HEAT_TPU_SLO_FAST_BURN": ("float", "14", "fast-window burn-rate factor an SLO must exceed to fire (error budget consumed 14x faster than allowed)"),
    "HEAT_TPU_SLO_SLOW_BURN": ("float", "2", "slow-window burn-rate factor an SLO must also exceed to fire (both windows must burn)"),
    "HEAT_TPU_SLO_LATENCY_MS": ("float", "25", "default serving latency objective: serving.latency_ms p99 must stay under this many milliseconds"),
    "HEAT_TPU_SLO_SHED_PCT": ("float", "1", "default serving shed objective: shed requests (quota + queue) must stay under this percent of admitted+shed"),
    "HEAT_TPU_SLO_HEARTBEAT_S": ("float", "0", "fit.heartbeat_ts freshness objective in seconds (0 = heartbeat SLO not installed; serving-only processes have no fit heartbeat)"),
    "HEAT_TPU_ALERT_RING": ("int", "256", "capacity of the alert fired/resolved transition ring (/sloz, /statusz, crash bundles)"),
    "HEAT_TPU_JOURNAL_RING": ("int", "256", "capacity of the control-plane decision-journal hot ring (/decisionz, cross-worker snapshots, crash bundles)"),
    "HEAT_TPU_JOURNAL_DIR": ("str", "", "durable decision-journal directory: every journal event also commits as an immutable atomic+CRC jsonl segment there, replayable after the process dies via python -m heat_tpu.telemetry.replay (empty = hot ring only)"),
    "HEAT_TPU_TSDB_INTERVAL_S": ("float", "1.0", "embedded metric-history sampler interval: seconds between registry scrapes into the /queryz ring buffers"),
    "HEAT_TPU_TSDB_RETENTION": ("int", "512", "points retained per metric-history series (memory is series x retention x two floats, strictly bounded)"),
    "HEAT_TPU_TSDB_SERIES": ("str", "", "comma-separated allowlist of registry series the TSDB sampler scrapes (trailing * = prefix match); empty = the curated control-plane default set (slo.*, serve.*, drift.*, canary.*, fleet.*, qos.*, stream.*, journal.*, alerts.*, dispatch.compile_fallbacks)"),
    "HEAT_TPU_SKETCH": ("bool", "1", "input-drift sketches on the /v1/predict path: per-feature moments + log-bucket histograms folded per coalesced batch off the caller's latency path"),
    "HEAT_TPU_DRIFT_THRESHOLD": ("float", "0.25", "PSI score above which a served model's input distribution counts as drifted (fires the drift:<model> alert and flips its /healthz status)"),
    "HEAT_TPU_DRIFT_MIN_ROWS": ("int", "200", "rows the live sketch must hold before a drift score is reported (small-sample PSI is noise: ~0.2 at 100 in-distribution rows against a 0.25 threshold)"),
    # -- resilience (heat_tpu/resilience, docs/resilience.md) -----------
    "HEAT_TPU_FAULT_PLAN": ("str", "", "fault-injection plan: inline JSON or a path to a JSON file"),
    "HEAT_TPU_RETRY_NO_SLEEP": ("bool", "0", "record retry backoff delays without sleeping (deterministic failure tests)"),
    "HEAT_TPU_IO_RETRY_ATTEMPTS": ("int", "3", "max attempts of the io load/save retry policy"),
    "HEAT_TPU_IO_RETRY_BASE_DELAY": ("float", "0.05", "first backoff delay (s) of the io retry policy"),
    "HEAT_TPU_IO_RETRY_MAX_DELAY": ("float", "2.0", "backoff delay cap (s) of the io retry policy"),
    "HEAT_TPU_INIT_RETRY_ATTEMPTS": ("int", "3", "max attempts of the parallel.init() bootstrap retry policy"),
    "HEAT_TPU_INIT_RETRY_BASE_DELAY": ("float", "0.5", "first backoff delay (s) of the init retry policy"),
    "HEAT_TPU_INIT_RETRY_MAX_DELAY": ("float", "10.0", "backoff delay cap (s) of the init retry policy"),
    "HEAT_TPU_IO_CHECKSUM": ("bool", "1", "CRC32 sidecar writing + load-side verification on every io path"),
    # -- elastic (heat_tpu/elastic, docs/elasticity.md) -----------------
    "HEAT_TPU_ELASTIC_MAX_RECOVERIES": ("int", "2", "how many worker-loss recoveries (reshape + resume) the elastic supervisor attempts before re-raising"),
    "HEAT_TPU_ELASTIC_MIN_WORLD": ("int", "1", "smallest world size the elastic supervisor may reshape down to"),
    "HEAT_TPU_ELASTIC_HEARTBEAT_TIMEOUT_S": ("float", "0", "declare a worker lost when its fit heartbeat is older than this many seconds (0 = liveness detection off, exit-code detection only)"),
    "HEAT_TPU_ELASTIC_POLL_S": ("float", "0.5", "polling interval of the elastic supervisor's heartbeat monitor"),
    "HEAT_TPU_HEARTBEAT_FILE": ("path", "", "touch this file at every resumable-fit chunk boundary (the cross-process liveness signal the elastic process supervisor watches)"),
    # -- AOT executable cache (core/aot_cache.py, docs/fleet.md) --------
    "HEAT_TPU_AOT_CACHE": ("path", "", "persistent on-disk AOT executable cache directory: dispatch cache misses load serialized compiled artifacts instead of compiling, and fresh compiles are persisted for the next process (empty = off)"),
    "HEAT_TPU_AOT_SAVE": ("bool", "1", "whether an armed AOT cache may write artifacts (0 = read-only: replicas load the fleet's artifacts, only a designated writer populates them)"),
    # -- fleet (heat_tpu/fleet, docs/fleet.md) --------------------------
    "HEAT_TPU_FLEET_RETRIES": ("int", "3", "bounded failover attempts of one routed /v1/predict across healthy replicas (connect error / 5xx / timeout each consume one)"),
    "HEAT_TPU_FLEET_TIMEOUT_S": ("float", "10", "per-replica timeout of one proxied request before the router fails over"),
    "HEAT_TPU_FLEET_CB_FAILURES": ("int", "3", "consecutive failures after which a replica's circuit breaker ejects it from routing"),
    "HEAT_TPU_FLEET_CB_COOLDOWN_S": ("float", "2.0", "seconds an ejected replica waits before the circuit breaker admits one half-open probe request"),
    "HEAT_TPU_FLEET_HEALTH_PERIOD_S": ("float", "0.5", "router health-poll interval: each replica's /readyz is scraped this often for readiness, drain state and its model list"),
    "HEAT_TPU_FLEET_RATE": ("float", "0", "fleet-global token-bucket admission refill (rows/s) at the router — one bucket for the whole replica set, not per replica; 0 = unlimited"),
    "HEAT_TPU_FLEET_BURST": ("float", "256", "fleet-global token-bucket burst capacity (rows)"),
    "HEAT_TPU_FLEET_LOAD_FACTOR": ("float", "1.5", "bounded-load consistent hashing factor: the hash-affine replica is skipped for the next in preference order when its in-flight count exceeds factor x the ready-replica average + 1"),
    "HEAT_TPU_FLEET_DRAIN_TIMEOUT_S": ("float", "10", "longest a draining replica waits for in-flight work to finish before closing anyway"),
    "HEAT_TPU_FLEET_MIN_REPLICAS": ("int", "1", "autoscaler floor on the replica count"),
    "HEAT_TPU_FLEET_MAX_REPLICAS": ("int", "4", "autoscaler ceiling on the replica count"),
    "HEAT_TPU_FLEET_TICK_S": ("float", "1.0", "autoscaler evaluation interval"),
    "HEAT_TPU_FLEET_UP_TICKS": ("int", "2", "consecutive overloaded ticks required before one scale-up (hysteresis)"),
    "HEAT_TPU_FLEET_DOWN_TICKS": ("int", "5", "consecutive underloaded ticks required before one scale-down (hysteresis)"),
    "HEAT_TPU_FLEET_P99_UP_MS": ("float", "50", "scale-up signal: routed p99 latency (sliding window) above this many ms counts a tick overloaded"),
    "HEAT_TPU_FLEET_P99_DOWN_MS": ("float", "10", "scale-down signal: routed p99 latency must be below this many ms for a tick to count underloaded"),
    "HEAT_TPU_FLEET_INFLIGHT_UP": ("float", "8", "scale-up signal: mean in-flight requests per ready replica above this counts a tick overloaded"),
    "HEAT_TPU_FLEET_INFLIGHT_DOWN": ("float", "1", "scale-down signal: mean in-flight per ready replica must be below this for a tick to count underloaded"),
    # -- serving (heat_tpu/serving, docs/serving.md) --------------------
    "HEAT_TPU_SHADOW_FRACTION": ("float", "0", "fraction of admitted coalesced predict batches shadow-mirrored to the loaded canary version (systematic per-batch sampling, off the caller's latency path; 0 = shadowing off)"),
    "HEAT_TPU_SHADOW_QUEUE": ("int", "8", "bounded depth (batches) of the shadow-mirror queue; a full queue drops the mirrored batch (counted in canary.dropped) so shadowing can never back-pressure the primary path"),
    "HEAT_TPU_CANARY_MIN_ROWS": ("int", "256", "shadow rows the canary comparator must accumulate before the decision engine renders its first verdict"),
    "HEAT_TPU_CANARY_MAX_MISMATCH_PCT": ("float", "1", "mismatched-row budget (percent) for tolerance-policy kinds before a canary fails; bitwise kinds allow zero mismatches regardless"),
    "HEAT_TPU_CANARY_LATENCY_X": ("float", "3", "canary per-row inference-latency budget as a multiple of the primary's measured time on the same mirrored batches; exceeding it fails the canary"),
    "HEAT_TPU_CANARY_AUTO": ("bool", "1", "whether the canary decision engine may mutate the registry (auto-promote on pass, auto-rollback on fail); 0 = observe-only (verdicts and events still recorded)"),
    "HEAT_TPU_CANARY_RING": ("int", "128", "capacity of the retained canary comparison/decision event ring (/canaryz, /statusz, snapshots, crash bundles)"),
    "HEAT_TPU_SERVE_MAX_BATCH": ("int", "64", "largest coalesced inference batch (rows) and the top pad-to-bucket shape; also the largest single request"),
    "HEAT_TPU_SERVE_MAX_DELAY_MS": ("float", "2.0", "longest a queued predict request waits for batch-mates before its coalesced dispatch (the latency/throughput dial)"),
    "HEAT_TPU_SERVE_QUEUE_DEPTH": ("int", "256", "admission bound: rows queued-or-in-flight across the service before requests shed with OverloadedError/429"),
    "HEAT_TPU_SERVE_RATE": ("float", "0", "default per-tenant token-bucket refill (rows/s); 0 = unlimited (tenants without an explicit set_quota are not rate-limited)"),
    "HEAT_TPU_SERVE_BURST": ("float", "64", "default per-tenant token-bucket burst capacity (rows)"),
    # -- QoS scheduling (docs/serving.md "QoS scheduling") --------------
    "HEAT_TPU_QOS_DEFAULT_CLASS": ("choice", "standard", "priority class of tenants without an explicit set_class: latency | standard | batch"),
    "HEAT_TPU_QOS_LATENCY_RESERVED_PCT": ("float", "20", "percent of HEAT_TPU_SERVE_QUEUE_DEPTH reserved for the latency lane: standard/batch requests queue-shed once total depth crosses (100 - this)% of the bound, so latency-class admission can never be starved by lower lanes"),
    "HEAT_TPU_QOS_BATCH_LIMIT_PCT": ("float", "60", "percent of HEAT_TPU_SERVE_QUEUE_DEPTH at which batch-class requests queue-shed (strict class ordering at the depth gate: batch sheds first, then standard, latency last)"),
    "HEAT_TPU_QOS_DEADLINE_LATENCY_MS": ("float", "10", "class-default coalescing deadline budget (ms) of a latency-class request without an explicit deadline_ms"),
    "HEAT_TPU_QOS_DEADLINE_STANDARD_MS": ("float", "50", "class-default coalescing deadline budget (ms) of a standard-class request without an explicit deadline_ms"),
    "HEAT_TPU_QOS_DEADLINE_BATCH_MS": ("float", "1000", "class-default coalescing deadline budget (ms) of a batch-class request without an explicit deadline_ms"),
    "HEAT_TPU_QOS_PREEMPT_ON_LATENCY": ("bool", "0", "arm the preemption gate from admission: each admitted latency-class request asks running checkpointed batch fits to yield at their next resumable-fit chunk boundary (cleared when the latency lane drains empty)"),
    "HEAT_TPU_QOS_METER": ("bool", "1", "per-tenant cost metering on the serving path: each coalesced batch's executable FLOPs/bytes and device-ms are attributed to its member tenants pro rata by rows (/tenantz)"),
    # -- streaming (heat_tpu/streaming, docs/streaming.md) --------------
    "HEAT_TPU_STREAM_WINDOW": ("int", "256", "rows per stream fit window (the resumable-fit chunk unit of the online estimators); windows are fixed-size so a resumed consumer replays the identical window sequence from its committed offset"),
    "HEAT_TPU_STREAM_SEGMENT_ROWS": ("int", "4096", "rows per segment file of the file-backed stream log (FileSegmentLog append granularity; reads may span segments)"),
    "HEAT_TPU_STREAM_PREFETCH": ("int", "2", "device-staging look-ahead depth (windows) of the stream consumer's prefetch_to_device pipeline from the stream head"),
    "HEAT_TPU_STREAM_COMMIT_EVERY": ("int", "1", "stream windows per atomic offset+model checkpoint commit of an online fit (the kill+resume replay granularity)"),
    "HEAT_TPU_STREAM_RESHARD_PSI": ("float", "0.25", "PSI of the incoming key distribution (rolling recent windows vs the accumulated stable reference) above which the consumer triggers a windowed reshard of split-axis staging"),
    "HEAT_TPU_STREAM_REFRESH_MIN_S": ("float", "0", "cooldown (seconds) between drift-triggered model refreshes of the same model; 0 = refresh on every firing drift alert check"),
    # -- overlap / nn (docs/overlap.md) ---------------------------------
    "HEAT_TPU_ASYNC_CKPT": ("bool", "1", "asynchronous checkpoint writes in resumable fits (0 = fully synchronous saves)"),
    "HEAT_TPU_GRAD_BUCKET_MB": ("float", "4", "byte bound (MiB) of one bucketed gradient-reduction psum"),
    "HEAT_TPU_FLASH": ("bool", "1", "flash-attention kernel for local attention on TPU (0 = einsum path)"),
    # -- kernels / linalg -----------------------------------------------
    "HEAT_TPU_HSVD_PRECISION": ("choice", "high", "hsvd Gram-pass matmul precision: default | high | highest"),
    "HEAT_TPU_HSVD_SYRK": ("bool", "1", "one-HBM-read syrk kernel for hsvd Gram passes when supported"),
    "HEAT_TPU_HSVD_BATCHED": ("bool", "0", "opt-in batched (vmapped) leaf factorizations in the hsvd merge tree: one stacked gram+eigh over the equal-shape leaf blocks instead of the sequential per-leaf loop (the 'can't fuse eigh' A/B, scripts/bench.py hsvd)"),
    # -- sparse (heat_tpu/sparse) ---------------------------------------
    "HEAT_TPU_SPGEMM_DENSE_DENSITY": ("float", "0.5", "estimated-output-density threshold at which sparse@sparse matmul falls back from the output-sparse triplet ring to the GEMM-style dense route (estimate: 1 - exp(-nnz_A*nnz_B/(m*k*n)); 1.0 = always ring, 0.0 = always dense)"),
    # -- fft (docs/fft_roofline.md) -------------------------------------
    "HEAT_TPU_PLANAR": ("bool", "0", "planar (re, im) FFT engine: 1 = transforms run on two real planes (the leading-contraction engine and its Pallas stage kernels); 0 = native complex through jnp.fft"),
    "HEAT_TPU_FFT_PRECISION": ("choice", "high", "matmul precision of the planar FFT engine (HEAT_TPU_PLANAR=1): default | high | highest; unset, its interleaved and leading-axis engines run at high and only the per-axis fallback (_planar.fft1) at highest"),
    "HEAT_TPU_FFT_CUTOFF": ("int", "64", "extent cutoff below which planar FFT uses the direct DFT matmul"),
    "HEAT_TPU_FFT_DIRECT_CAP": ("int", "1024", "largest extent the direct DFT path may handle"),
    "HEAT_TPU_FFT_PALLAS": ("bool", "0", "opt-in Pallas planar-FFT stage kernel"),
    "HEAT_TPU_FFT_INTERLEAVED": ("bool", "1", "interleaved pencil decomposition of multi-axis FFTs"),
    "HEAT_TPU_FFT_WEIGHT_CACHE_MB": ("float", "256", "byte bound (MiB) of the shared FFT twiddle/weight LRU cache"),
    "HEAT_TPU_FFT_STAGE_PALLAS": ("bool", "1", "Pallas four-step stage kernel of the leading-axis FFT"),
    "HEAT_TPU_FFT_EXT_PALLAS": ("bool", "1", "Pallas extension kernel of the leading-axis FFT"),
    "HEAT_TPU_FFT_LEADING": ("bool", "1", "leading-axis (split-axis) FFT path"),
    # -- test / CI harness ----------------------------------------------
    "HEAT_TPU_TEST_DEVICES": ("int", "8", "virtual CPU mesh size the test suite forces (tests/conftest.py)"),
}

_FALSE_WORDS = ("0", "false", "no", "off")


def registered_knobs() -> Dict[str, tuple]:
    """Copy of the knob table (name -> (type, default, doc))."""
    return dict(KNOBS)


def _lookup(name: str) -> tuple:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"{name!r} is not a registered HEAT_TPU knob; add it to "
            "heat_tpu.core._env.KNOBS (name, type, default, doc) — the "
            "H201 lint rule enforces the same registry on direct "
            "os.environ reads"
        ) from None


def knob_default(name: str) -> str:
    """The registered default (string form) of ``name``."""
    return _lookup(name)[1]


def env_str(name: str, default: Optional[str] = None) -> str:
    """Raw string value of a registered knob (default from the table)."""
    d = _lookup(name)[1] if default is None else default
    return os.environ.get(name, d)


def env_flag(name: str, default: Optional[bool] = None) -> bool:
    """Boolean knob: unset -> registered default; ``0/false/no/off``
    (any case) -> False; anything else -> True."""
    v = os.environ.get(name)
    if v is None:
        if default is not None:
            return default
        v = _lookup(name)[1]
    return str(v).strip().lower() not in _FALSE_WORDS


def env_int(name: str, default: Optional[int] = None) -> int:
    """Integer knob (registered default when unset)."""
    v = os.environ.get(name)
    if v is None:
        return int(_lookup(name)[1]) if default is None else default
    return int(v)


def env_float(name: str, default: Optional[float] = None) -> float:
    """Float knob (registered default when unset)."""
    v = os.environ.get(name)
    if v is None:
        return float(_lookup(name)[1]) if default is None else default
    return float(v)


# ----------------------------------------------------------------------
# shared precision tables (FFT + hsvd)
# ----------------------------------------------------------------------
_PRECISION_TABLE = {
    "default": jax.lax.Precision.DEFAULT,
    "high": jax.lax.Precision.HIGH,
    "highest": jax.lax.Precision.HIGHEST,
}


def precision_name_from_env(var: str, default: str) -> str:
    """Normalized precision name from an env var with a diagnostic error."""
    name = os.environ.get(var, default).strip().lower()
    if name not in _PRECISION_TABLE:
        raise ValueError(
            f"{var}={os.environ.get(var)!r}: expected one of {sorted(_PRECISION_TABLE)}"
        )
    return name


def precision_from_env(var: str, default: str):
    """``jax.lax.Precision`` from an env var with a diagnostic error."""
    return _PRECISION_TABLE[precision_name_from_env(var, default)]
