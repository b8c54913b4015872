"""Generate the API reference markdown from live docstrings.

The reference ships a Sphinx autodoc tree; this environment has no
sphinx, so the same information — every public export per module with
its signature and summary line — is extracted with ``inspect`` into one
markdown page that ``build_docs.py`` renders into the site.

    python scripts/build_api_docs.py [--out docs/api_reference.md]
"""

import argparse
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

#: (section title, module path, note)
MODULES = [
    ("Top level", "heat_tpu", "factories, arithmetics, manipulations and the rest of the numpy-style surface"),
    ("Dispatch", "heat_tpu.core.dispatch", "cached-executable dispatch, chain fusion, buffer donation (docs/dispatch.md)"),
    ("Resilience", "heat_tpu.resilience", "fault injection, retry policies, atomic IO, divergence guards (docs/resilience.md)"),
    ("Overlap", "heat_tpu.utils.overlap", "async checkpointing, device prefetch + bucketed gradient-reduction counters (docs/overlap.md)"),
    ("Observability", "heat_tpu.telemetry", "unified metrics registry, structured spans, comm-volume accounting (docs/observability.md)"),
    ("Request tracing", "heat_tpu.telemetry.tracing", "request-scoped distributed tracing: trace context + handoff helpers, tail-sampled trace store, /tracez + exemplars (docs/observability.md)"),
    ("SLO monitors", "heat_tpu.telemetry.slo", "declarative objectives with multi-window burn-rate alerting over the bounded histograms (/sloz; docs/observability.md)"),
    ("Input-drift sketches", "heat_tpu.telemetry.sketch", "streaming per-feature moment + log-bucket sketches, PSI/KL divergence vs persisted baselines (/driftz; docs/observability.md)"),
    ("Alerts", "heat_tpu.telemetry.alerts", "deduplicated severity-tagged fired/resolved alert events with exemplar trace ids (docs/observability.md)"),
    ("Decision journal", "heat_tpu.telemetry.journal", "typed control-plane decision events with causal links + evidence, bounded hot ring + durable atomic/CRC segment log (/decisionz; docs/observability.md)"),
    ("Metric history (TSDB)", "heat_tpu.telemetry.tsdb", "embedded fixed-interval metric history: allowlisted series sampled into bounded rings, range queries + window stats (/queryz; docs/observability.md)"),
    ("Journal replay", "heat_tpu.telemetry.replay", "offline reconstruction of the decision timeline and causal chains from a durable journal directory (python -m heat_tpu.telemetry.replay; docs/observability.md)"),
    ("Static analysis", "heat_tpu.analysis", "SPMD program lint (J101-J105) + framework-invariant AST lint (H101-H601, H701-H705) (docs/static_analysis.md)"),
    ("Dtype-flow lint", "heat_tpu.analysis.dtype_flow", "jaxpr precision lint: silent truncation, low-precision accumulation, unpinned contractions, policy violations (J201-J204; docs/static_analysis.md)"),
    ("Peak-HBM estimator", "heat_tpu.analysis.memory_model", "static per-device peak-memory prediction from the jaxpr (liveness + donation + sharding), J301 against HEAT_TPU_HBM_BUDGET_BYTES (docs/static_analysis.md)"),
    ("Precision policies", "heat_tpu.analysis.precision_policy", "the per-estimator bitwise/tolerance POLICIES registry and its three enforcement choke points (docs/static_analysis.md)"),
    ("Concurrency sanitizer", "heat_tpu.analysis.tsan", "runtime lock-order/unguarded-access sanitizer over the central LOCK_REGISTRY (HEAT_TPU_TSAN; docs/static_analysis.md)"),
    ("Control-plane protocols", "heat_tpu.analysis.protocols", "pure-literal PROTOCOLS registry: every controller's declared state machine, journal vocabulary constants, temporal PROPERTIES (docs/static_analysis.md)"),
    ("Protocol model checker", "heat_tpu.analysis.model_check", "bounded exhaustive check of the declared machines against the adversarial environment; counterexamples as synthetic causal journal chains (python -m heat_tpu.analysis.model_check; docs/static_analysis.md)"),
    ("Protocol conformance", "heat_tpu.analysis.conformance", "runtime stepping of live journal events through the declared machines, H805 on illegal transitions (HEAT_TPU_PROTOCOL_CHECK; docs/static_analysis.md)"),
    ("Elastic", "heat_tpu.elastic", "worker-loss detection, mesh reshape + cross-world resume supervision (docs/elasticity.md)"),
    ("Serving", "heat_tpu.serving", "online inference: model registry + hot-load, request coalescing with pad-to-bucket dispatch, per-tenant admission control, /v1 HTTP endpoints (docs/serving.md)"),
    ("Fleet", "heat_tpu.fleet", "fleet-scale serving: fault-tolerant replica router (consistent-hash affinity, circuit breakers, bounded-retry failover), replica process management, load-driven elastic autoscaling (docs/fleet.md)"),
    ("Streaming", "heat_tpu.streaming", "streaming continuous learning: replayable sources (durable segment log), windowed exactly-once consumer, online fits with bitwise kill+resume, drift-triggered refresh driver (docs/streaming.md)"),
    ("AOT cache", "heat_tpu.core.aot_cache", "persistent on-disk AOT executable cache: serialized compiled artifacts keyed by the dispatch operand-spec keys, fingerprint-invalidated (docs/fleet.md)"),
    ("Lock registry", "heat_tpu.analysis.concurrency", "central registry of cross-thread locks and the structures they guard (the H7xx rules and the sanitizer share it)"),
    ("Communication", "heat_tpu.parallel.comm", "mesh/communication layer"),
    ("Linear algebra", "heat_tpu.core.linalg.basics", None),
    ("QR / SVD / solvers", "heat_tpu.core.linalg.qr", None),
    ("Hierarchical SVD", "heat_tpu.core.linalg.svdtools", None),
    ("Solvers", "heat_tpu.core.linalg.solver", None),
    ("FFT", "heat_tpu.fft.fft", None),
    ("Sparse", "heat_tpu.sparse", None),
    ("Clustering", "heat_tpu.cluster", None),
    ("Classification", "heat_tpu.classification", None),
    ("Decomposition", "heat_tpu.decomposition", None),
    ("Preprocessing", "heat_tpu.preprocessing", None),
    ("Regression", "heat_tpu.regression", None),
    ("Naive Bayes", "heat_tpu.naive_bayes", None),
    ("Spatial", "heat_tpu.spatial", None),
    ("Graph", "heat_tpu.graph", None),
    ("Neural nets", "heat_tpu.nn", None),
    ("Optimizers", "heat_tpu.optim", None),
    ("IO", "heat_tpu.core.io", None),
    ("Random", "heat_tpu.core.random", None),
    ("Statistics", "heat_tpu.core.statistics", None),
    ("Signal", "heat_tpu.core.signal", None),
    ("Data utilities", "heat_tpu.utils.data", None),
    ("Checkpointing", "heat_tpu.utils.checkpoint", None),
    ("Profiling", "heat_tpu.utils.profiling", None),
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def _summary(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    line = doc.strip().split("\n", 1)[0].strip()
    return line


def document_module(modpath: str):
    import importlib

    mod = importlib.import_module(modpath)
    names = getattr(mod, "__all__", None)
    if not names:
        names = [n for n in dir(mod) if not n.startswith("_")]
        names = [
            n for n in names
            if getattr(getattr(mod, n, None), "__module__", "").startswith("heat_tpu")
            or inspect.isroutine(getattr(mod, n, None))
        ]
    rows = []
    for n in sorted(set(names)):
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if inspect.isclass(obj):
            rows.append((f"class {n}", _summary(obj)))
            for mn, mobj in sorted(inspect.getmembers(obj, inspect.isfunction)):
                if mn.startswith("_"):
                    continue
                rows.append((f"{n}.{mn}{_sig(mobj)}", _summary(mobj)))
        elif inspect.isroutine(obj):
            rows.append((f"{n}{_sig(obj)}", _summary(obj)))
        else:
            rows.append((n, type(obj).__name__))
    return rows


def build_env_vars(out_path: str) -> int:
    """Generate ``docs/env_vars.md`` from the central knob registry
    (``heat_tpu.core._env.KNOBS``) — the same table the typed accessors
    and the H201 lint rule enforce, so the docs cannot drift from the
    code.  Returns the number of documented knobs."""
    from heat_tpu.core._env import KNOBS

    lines = [
        "# Environment variables",
        "",
        "Generated from the central knob registry (`heat_tpu/core/_env.py"
        " KNOBS`) by `scripts/build_api_docs.py` — do not edit.",
        "",
        "Every `HEAT_TPU_*` knob the framework reads is registered in that"
        " one table (name, type, default, doc); the typed accessors"
        " (`env_flag`/`env_int`/`env_float`/`env_str`) refuse unregistered"
        " names and the AST linter's [H201 rule](static_analysis.md) flags"
        " any direct `os.environ` read of an unregistered `HEAT_TPU_*`"
        " literal — so this page is complete by construction.",
        "",
        "Boolean knobs treat `0/false/no/off` (any case) as off and"
        " anything else as on.  An empty default means *unset* (the"
        " consumer auto-detects).",
        "",
        "| variable | type | default | effect |",
        "|---|---|---|---|",
    ]
    for name in sorted(KNOBS):
        typ, default, doc = KNOBS[name]
        shown = f"`{default}`" if default != "" else "*(unset)*"
        lines.append(f"| `{name}` | {typ} | {shown} | {doc} |")
    lines += [
        "",
        "See also: [static analysis](static_analysis.md),"
        " [dispatch layer](dispatch.md), [resilience](resilience.md),"
        " [overlap layer](overlap.md), [observability](observability.md).",
        "",
    ]
    with open(out_path, "w") as f:
        f.write("\n".join(lines))
    return len(KNOBS)


#: markers bounding the generated endpoint-index block inside
#: docs/observability.md (everything between them is regenerated)
ENDPOINT_BEGIN = "<!-- BEGIN GENERATED: endpoint-index (scripts/build_api_docs.py) -->"
ENDPOINT_END = "<!-- END GENERATED: endpoint-index -->"


def build_endpoint_index(doc_path: str) -> int:
    """Regenerate the endpoint-index table in ``docs/observability.md``
    from the server's declarative route registry
    (``heat_tpu.telemetry.server.BUILTIN_ROUTES``) — one source of
    truth, so a new route cannot ship without its docs row.  Returns the
    number of routes written."""
    from heat_tpu.telemetry.server import BUILTIN_ROUTES

    rows = [
        "| route | purpose | knobs |",
        "|---|---|---|",
    ]
    for r in BUILTIN_ROUTES:
        knobs = ", ".join(f"`{k}`" for k in r["knobs"]) or "—"
        purpose = str(r["purpose"]).replace("|", "\\|")
        rows.append(f"| `{r['route']}` | {purpose} | {knobs} |")
    with open(doc_path) as f:
        text = f.read()
    try:
        head, rest = text.split(ENDPOINT_BEGIN, 1)
        _, tail = rest.split(ENDPOINT_END, 1)
    except ValueError:
        raise SystemExit(
            f"{doc_path} is missing the endpoint-index markers "
            f"({ENDPOINT_BEGIN!r} ... {ENDPOINT_END!r})"
        )
    block = ENDPOINT_BEGIN + "\n" + "\n".join(rows) + "\n" + ENDPOINT_END
    with open(doc_path, "w") as f:
        f.write(head + block + tail)
    return len(BUILTIN_ROUTES)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "docs", "api_reference.md"))
    ap.add_argument("--env-out", default=os.path.join(REPO, "docs", "env_vars.md"))
    ap.add_argument(
        "--endpoints-doc",
        default=os.path.join(REPO, "docs", "observability.md"),
    )
    args = ap.parse_args()

    n_knobs = build_env_vars(args.env_out)
    print(f"env vars: {n_knobs} knobs -> {args.env_out}")

    n_routes = build_endpoint_index(args.endpoints_doc)
    print(f"endpoint index: {n_routes} routes -> {args.endpoints_doc}")

    parts = [
        "# API reference",
        "",
        "Generated from live docstrings by `scripts/build_api_docs.py` — do not edit.",
        "Reference `file:line` citations inside each docstring point at the",
        "upstream component the export mirrors.",
        "",
        "> **Note for `ht.jit` users:** executable caching and elementwise chain",
        "> fusion are now the DEFAULT behavior of the eager op surface — every op",
        "> dispatches through a cached compiled executable, and elementwise",
        "> chains defer and fuse into one XLA computation automatically (see",
        "> [dispatch.md](dispatch.md)).  `ht.jit` is still worth reaching for",
        "> when you want a whole pipeline — reductions, matmuls, control flow —",
        "> fused into a single program; for plain elementwise chains feeding a",
        "> reduction it no longer buys anything over the default path.",
        "",
    ]
    total = 0
    failures = []
    for title, modpath, note in MODULES:
        try:
            rows = document_module(modpath)
        except Exception as e:
            # a module that fails to import means a GUTTED reference —
            # record it and fail the build below instead of silently
            # publishing an incomplete page
            failures.append(f"{modpath}: {type(e).__name__}: {e}")
            continue
        parts.append(f"## {title} (`{modpath}`)")
        if note:
            parts.append(f"\n{note}\n")
        parts.append("")
        parts.append("| export | summary |")
        parts.append("|---|---|")
        for sig, summ in rows:
            sig_md = sig.replace("|", "\\|")
            summ_md = (summ or "").replace("|", "\\|")
            parts.append(f"| `{sig_md}` | {summ_md} |")
            total += 1
        parts.append("")
    with open(args.out, "w") as f:
        f.write("\n".join(parts))
    print(f"api reference: {total} entries -> {args.out}")
    if failures or total == 0:
        for msg in failures:
            print(f"FAILED module: {msg}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
