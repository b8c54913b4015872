"""Host time of the dispatch layer a solve: the mean duration of the program's
own ``hsvd.dispatch`` spans (jit cache lookup and enqueue of the solve's one
device program) over the window's solves, read from the program's span ring.

The window's solves are the last ``run["solves"]`` root spans
(``ht.linalg.hsvd``) on the calling thread, each with the one dispatch span
inside it: the warm-up solves lie before them and the reference runs after
the readers.  ``api_host_ms`` reads the same solves through ``window_solves``."""

import threading

ROOT, DISPATCH = "ht.linalg.hsvd", "hsvd.dispatch"


def window_solves(run, metric):
    """[(root, dispatch), ...] of the window's solves, or None with the
    reason in ``run["notes"]``: never a number that was not read."""
    from heat_tpu.telemetry import get_spans

    me, n = threading.get_ident(), run["solves"]
    mine = [r for r in get_spans() if r.thread_id == me]
    pairs = list(zip([r for r in mine if r.name == ROOT][-n:], [r for r in mine if r.name == DISPATCH][-n:]))
    if len(pairs) < n:
        run["notes"][metric] = (f"{len(pairs)} {ROOT} spans with their {DISPATCH} in the ring for {n} solves"
                                " (no such span, tracing off, or a ring that wrapped)")
        return None
    if any(d.start_ns < r.start_ns or d.start_ns + d.duration_ns > r.start_ns + r.duration_ns for r, d in pairs):
        run["notes"][metric] = f"a {DISPATCH} span outside its {ROOT}: the ring holds other solves than the window's"
        return None
    return pairs


def read(run):
    pairs = window_solves(run, "dispatch_enqueue_ms")
    if pairs is None:
        return None
    return sum(d.duration_ns for _, d in pairs) / len(pairs) / 1e6
