"""Host time of the API layer a solve: the durations of the window's
``ht.preprocessing.*`` root spans (upstream's five functions are fourteen
``fit`` / ``transform`` / ``inverse_transform`` calls), summed a solve: the
time the calls hold their caller while the device's work is merely queued.  A
host read that creeps into a fit shows here as that fit's whole device time.
Read from the program's span ring; ``scalers_launches``, ``scalers_copies``
and ``quantile_passes`` read the same solves through ``window_roots``."""

import threading

PREFIX = "ht.preprocessing."
CALLS_A_SOLVE = 14  # 4 x (fit, transform, inverse_transform) + the Normalizer's fit and transform


def window_roots(run, metric):
    """The root spans of the window's solves, oldest first, or None with the
    reason in ``run["notes"]``: never a number that was not read.  The
    warm-up solves lie before them in the ring and the reference runs after
    the readers."""
    from heat_tpu.telemetry import get_spans

    me, want = threading.get_ident(), CALLS_A_SOLVE * run["solves"]
    roots = [r for r in get_spans() if r.thread_id == me and r.name.startswith(PREFIX)][-want:]
    if len(roots) < want or any("launches" not in r.attrs for r in roots):
        run["notes"][metric] = (f"{len(roots)} {PREFIX}* root spans with their counters in the ring for "
                                f"{run['solves']} solves of {CALLS_A_SOLVE} calls (no such span, tracing off, "
                                "or a ring that wrapped)")
        return None
    return roots


def read(run):
    roots = window_roots(run, "scalers_host_ms")
    if roots is None:
        return None
    return sum(r.duration_ns for r in roots) / run["solves"] / 1e6
