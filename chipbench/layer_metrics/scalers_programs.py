"""Programs a solve, counted where they are launched: the window's
``dispatch.launch`` spans (one a program through ``core/dispatch.py``, PR 35)
and the ``launches`` of its ``statistics.quantiles`` spans (the selection is
a jitted program of the consumer's own and keeps its own span), a solve.
Unlike ``scalers_launches``, which sums what the fourteen root spans saw, this
counts the programs that run outside every ``ht.*`` call too: a deferred
store or a fitted attribute's small chain that runs where the caller reads it.
``scalers_undonated_stores`` and ``scalers_dispatch_host_ms`` read the same
spans through ``window_launches``."""

import threading

from chipbench.run import load_py

LAUNCH, QUANTILES = "dispatch.launch", "statistics.quantiles"


def window_launches(run, metric):
    """``(launch spans, quantiles spans)`` of the calling thread that start at
    or after the start of the window's first root span, inside a root or not
    (the readers run before the reference does, so the ring ends with the
    window), or None with the reason in ``run["notes"]``."""
    from heat_tpu.telemetry import get_spans

    roots = load_py("layer_metrics", "scalers_host_ms").window_roots(run, metric)
    if roots is None:
        return None
    me, start = threading.get_ident(), roots[0].start_ns
    mine = [r for r in get_spans() if r.thread_id == me and r.start_ns >= start]
    launches = [r for r in mine if r.name == LAUNCH]
    if not launches:
        run["notes"][metric] = f"no {LAUNCH} span in the window's {len(mine)} spans (a program from before PR 35)"
        return None
    return launches, [r for r in mine if r.name == QUANTILES]


def read(run):
    found = window_launches(run, "scalers_programs")
    if found is None:
        return None
    launches, quantiles = found
    run["notes"]["scalers_programs_outside_spans"] = sum(r.depth == 0 for r in launches) / run["solves"]
    return (len(launches) + sum(q.attrs["launches"] for q in quantiles)) / run["solves"]
