"""Unified observability: metrics registry, structured spans, device traces.

The framework's answer to three production questions the reference
(instrumented only from the outside by ``perun``, SURVEY.md §5) cannot
ask: *how many bytes crossed ICI/DCN this fit, how long did we spend in
XLA compiles, and where did the wall-clock go?*

* :mod:`~heat_tpu.telemetry.metrics` — process-global named counters,
  gauges and bounded histograms.  The four legacy counter islands
  (``core.dispatch``, ``resilience``, ``utils.overlap``,
  ``nn.data_parallel``) register into it; their ``*_stats()`` functions
  are thin views; :func:`snapshot` returns everything in one document
  and :func:`expose` emits Prometheus text for scrape-based
  deployments.
* :mod:`~heat_tpu.telemetry.spans` — nestable host-side spans in a
  bounded ring buffer (``HEAT_TPU_TRACE=0`` disables), each doubling as
  a ``jax.profiler.TraceAnnotation`` so Xprof/perfetto device timelines
  attribute ops to framework operations;
  :func:`export_chrome_trace` writes ``chrome://tracing``-loadable JSON
  with zero extra deps.
* :mod:`~heat_tpu.telemetry.profiling` — ``start_trace``/``stop_trace``
  /``monitor`` device-trace hooks (moved from ``utils.profiling``,
  which re-exports them).
* :mod:`~heat_tpu.telemetry.server` — runtime-introspection HTTP
  endpoint (``HEAT_TPU_HTTP_PORT``; ``/metrics`` ``/varz`` ``/healthz``
  ``/trace`` ``/statusz`` on a daemon thread, off by default).
* :mod:`~heat_tpu.telemetry.slo` — declarative SLO monitors with
  multi-window burn-rate alerting over the bounded histograms
  (``/sloz``; ``HEAT_TPU_SLO_*``).
* :mod:`~heat_tpu.telemetry.sketch` — streaming input-drift sketches
  (per-feature moments + log-bucket histograms, PSI/KL vs a persisted
  baseline) for the serving path (``/driftz``; ``HEAT_TPU_SKETCH``).
* :mod:`~heat_tpu.telemetry.alerts` — deduplicated, severity-tagged
  fired/resolved alert events in a bounded ring, carrying exemplar
  trace ids (``HEAT_TPU_ALERT_RING``).
* :mod:`~heat_tpu.telemetry.aggregate` — cross-worker snapshot
  tagging/merging with straggler/skew gauges
  (``telemetry.straggler_score``).
* :mod:`~heat_tpu.telemetry.flight_recorder` — crash flight recorder
  (``HEAT_TPU_FLIGHT_RECORDER``): atomic CRC32-checksummed forensic
  bundles on unhandled exceptions, rendered by
  ``python -m heat_tpu.telemetry.inspect``.

Instrumentation wired through the stack: ``parallel.comm`` collectives
account trace-time payload bytes x participants into
``comm.bytes.{op}`` / ``comm.calls.{op}``; ``core.dispatch`` records
per-compile wall time into the ``dispatch.compile_ms`` histogram;
``core.base.resumable_fit_loop`` emits heartbeat spans and the
``fit.iter_rate`` gauge; checkpoint save/restore and the async writer
drain are spanned so ``overlap.ckpt_stall_ms`` is attributable.

``HEAT_TPU_METRICS_DUMP=<path>`` writes the final snapshot as JSON at
process exit (CI scraping).  See ``docs/observability.md``.
"""

from __future__ import annotations

import atexit
import os
import time as _time
from typing import Any, Dict, Optional

from . import metrics
from . import journal
from . import tsdb
from . import tracing
from . import spans
from . import profiling
from . import alerts
from . import slo
from . import sketch
from . import aggregate
from . import flight_recorder
from . import server
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    dump_json,
    expose,
    gauge,
    histogram,
    snapshot,
)
from .spans import (
    SpanRecord,
    chrome_trace_doc,
    clear_spans,
    export_chrome_trace,
    get_spans,
    record_span,
    set_tracing,
    span,
    tracing_enabled,
)
from .tracing import (
    TraceContext,
    bind_context,
    current_context,
    current_trace_id,
    request_span,
    tracez_report,
    use_context,
)
from .profiling import annotate, exchange_exposure, idle_by_span, monitor, start_trace, stop_trace, trace
from .aggregate import (
    gather_snapshots,
    merge_snapshots,
    tag_snapshot,
    write_worker_snapshot,
)
from .flight_recorder import dump_bundle
from .server import start_server, stop_server
from .alerts import active_alerts, alert_events, alerts_snapshot
from .journal import (
    DecisionEvent,
    causal_chain,
    decisionz_report,
    emit,
    journal_events,
    read_journal,
)
from .tsdb import (
    query,
    queryz_report,
    record,
    sample_once,
    start_sampler,
    stop_sampler,
    window_stats,
)
from .slo import (
    SLO,
    install_default_slos,
    parse_slo,
    register_slo,
    slo_report,
    start_monitor,
    stop_monitor,
)
from .sketch import SKETCHES, check_drift, drift_report, record_batch

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "DecisionEvent",
    "SKETCHES",
    "SLO",
    "SpanRecord",
    "TraceContext",
    "active_alerts",
    "alert_events",
    "alerts_snapshot",
    "annotate",
    "causal_chain",
    "check_drift",
    "decisionz_report",
    "emit",
    "journal_events",
    "query",
    "queryz_report",
    "read_journal",
    "record",
    "sample_once",
    "start_sampler",
    "stop_sampler",
    "window_stats",
    "drift_report",
    "install_default_slos",
    "parse_slo",
    "record_batch",
    "register_slo",
    "slo_report",
    "start_monitor",
    "stop_monitor",
    "bind_context",
    "chrome_trace_doc",
    "clear_spans",
    "counter",
    "current_context",
    "current_trace_id",
    "dump_bundle",
    "dump_json",
    "exchange_exposure",
    "expose",
    "export_chrome_trace",
    "gather_snapshots",
    "gauge",
    "get_spans",
    "histogram",
    "idle_by_span",
    "merge_snapshots",
    "monitor",
    "record_span",
    "request_span",
    "reset_all",
    "set_tracing",
    "snapshot",
    "span",
    "start_server",
    "start_trace",
    "stop_server",
    "stop_trace",
    "summary_line",
    "tag_snapshot",
    "trace",
    "tracez_report",
    "tracing_enabled",
    "use_context",
    "write_worker_snapshot",
]

#: legacy per-domain reset functions delegate here with these names;
#: a domain maps to the registry prefixes it owns
_DOMAIN_PREFIXES = {
    "dispatch": ("dispatch.",),
    "faults": ("fault.",),
    "retry": ("retry.",),
    "resilience": ("fault.", "retry."),
    "overlap": ("overlap.",),
    "comm": ("comm.",),
    "fit": ("fit.",),
    "spans": ("spans.",),
    "tracing": ("tracing.",),
    "flight": ("flight.",),
    "checkpoint": ("checkpoint.",),
    "alerts": ("alerts.",),
    "slo": ("slo.",),
    "drift": ("drift.",),
    "journal": ("journal.",),
    "tsdb": ("tsdb.",),
    "telemetry": ("spans.", "tracing.", "fit.", "telemetry.", "flight.",
                  "checkpoint.", "alerts.", "slo.", "drift.", "journal.", "tsdb."),
}


def reset_all(domain: Optional[str] = None) -> None:
    """Zero telemetry state in one call.

    With no argument: every registered metric (dispatch, resilience,
    overlap, comm, fit, ...) AND the span ring buffer AND the tail-
    sampled trace store — the single replacement for the four legacy
    reset conventions.  With a domain name (``"dispatch"``,
    ``"resilience"``, ``"overlap"``, ``"comm"``, ...), only that
    island's metrics; the legacy ``reset_stats`` /
    ``reset_fault_stats`` / ``reset_retry_stats`` /
    ``reset_overlap_stats`` functions delegate here per-domain."""
    if domain is None:
        metrics.reset(None)
        spans.clear_spans()
        tracing.reset_store()
        alerts.clear_alerts()
        slo.reset_monitors()
        sketch.SKETCHES.clear()
        journal.reset_journal()
        tsdb.reset_tsdb()
        return
    prefixes = _DOMAIN_PREFIXES.get(domain)
    if prefixes is None:
        raise ValueError(
            f"unknown telemetry domain {domain!r}; known: {sorted(_DOMAIN_PREFIXES)}"
        )
    for p in prefixes:
        metrics.reset(p)
    if domain in ("spans", "telemetry"):
        spans.clear_spans()
    if domain in ("tracing", "telemetry"):
        tracing.reset_store()
    if domain in ("alerts", "telemetry"):
        alerts.clear_alerts()
    if domain in ("slo", "telemetry"):
        slo.reset_monitors()
    if domain in ("drift", "telemetry"):
        sketch.SKETCHES.clear()
    if domain in ("journal", "telemetry"):
        journal.reset_journal()
    if domain in ("tsdb", "telemetry"):
        tsdb.reset_tsdb()


def summary_line(iter_rate: Optional[float] = None) -> str:
    """One-line human summary of the headline metrics — the string the
    example scripts print after a fit: cumulative collective traffic
    (trace-time model, bytes x participants), total XLA compile wall
    time, and the last fit iteration rate (``fit.iter_rate`` gauge, or
    the explicit ``iter_rate`` argument for fast-path fits that never
    touch the gauge)."""
    snap = metrics.snapshot()
    comm_bytes = sum(
        v for k, v in snap.items()
        if k.startswith("comm.bytes.") and isinstance(v, (int, float))
    )
    compile_doc = snap.get("dispatch.compile_ms") or {}
    compile_ms = float(compile_doc.get("sum") or 0.0)
    if iter_rate is None:
        rate = snap.get("fit.iter_rate") or 0.0
    else:
        rate = iter_rate
    rate_s = f"{rate:.1f} iter/s" if rate else "n/a"
    return (
        f"telemetry: comm {comm_bytes / 2**30:.4f} GiB · "
        f"compile {compile_ms:.0f} ms · iter rate {rate_s}"
    )


@atexit.register
def _dump_at_exit() -> None:  # pragma: no cover - exercised via subprocess
    """``HEAT_TPU_METRICS_DUMP=<path>``: write the final metrics snapshot
    as JSON at interpreter exit (checked at exit time, so setting the
    variable after import still works).  The write goes through the
    resilience atomic+CRC32 writer, so a crash mid-dump can never leave
    a truncated artifact."""
    path = os.environ.get("HEAT_TPU_METRICS_DUMP")
    if not path:
        return
    try:
        metrics.dump_json(path)
    except Exception:  # lint: allow H501(best-effort metrics dump at interpreter exit)
        pass


def build_info_labels() -> Dict[str, str]:
    """The binary's identity labels: heat_tpu version, jax/jaxlib
    versions, the active backend and device kind.  Resolved lazily by
    the ``build_info`` metric on its first read (``jax.devices()``
    initializes the backend; an import must not)."""
    from ..version import __version__ as _v

    labels: Dict[str, str] = {"version": str(_v)}
    try:
        import jax
        import jaxlib

        labels["jax"] = str(jax.__version__)
        labels["jaxlib"] = str(getattr(jaxlib, "__version__", "?"))
        labels["backend"] = str(jax.default_backend())
        devs = jax.devices()
        labels["device_kind"] = str(devs[0].device_kind) if devs else "none"
    except Exception:  # lint: allow H501(no working backend: identity degrades to the version labels)
        labels.setdefault("backend", "unavailable")
    return labels


#: satellite identity metrics on every scrape surface (/metrics, /varz,
#: /statusz): which binary produced these numbers, and since when.  The
#: start timestamp is a callback gauge so ``reset_all()`` cannot zero
#: the process's birth time.
_PROCESS_START_TS = _time.time()
metrics.info(
    "build_info",
    "binary identity: heat_tpu/jax/jaxlib versions, backend, device kind",
    fn=build_info_labels,
)
metrics.gauge(
    "process.start_ts",
    "unix timestamp this process imported heat_tpu.telemetry",
    fn=lambda: _PROCESS_START_TS,
)

# runtime introspection: HEAT_TPU_HTTP_PORT starts the HTTP endpoint,
# HEAT_TPU_FLIGHT_RECORDER arms the crash recorder — both off by
# default, both zero-cost when off (docs/observability.md)
server.maybe_start_from_env()
flight_recorder.maybe_install_from_env()
