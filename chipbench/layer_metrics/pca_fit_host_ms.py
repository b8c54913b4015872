"""Host time of the API layer a fit: the mean duration of the window's
``ht.decomposition.PCA.fit`` root spans, the time ``fit`` holds its caller
while the device's work is merely queued (the moments' program, the
solver's one program, the results' wrapping).  A host read that creeps into
``fit`` shows here as the whole solve.  Read from the program's span ring.

The window's solves are the last ``run["solves"]`` roots on the calling
thread: the warm-up fits lie before them and the reference runs after the
readers.  ``pca_table_reads`` and ``pca_products_roofline_pct`` read the same
roots.  A program without the span (an older one) leaves nothing to read:
None, and the reason in the notes."""

import threading

ROOT = "ht.decomposition.PCA.fit"


def window_solves(run, metric):
    """The window's root spans, or None with the reason in ``run["notes"]``:
    never a number that was not read."""
    from heat_tpu.telemetry import get_spans

    me, n = threading.get_ident(), run["solves"]
    roots = [r for r in get_spans() if r.thread_id == me and r.name == ROOT][-n:]
    if len(roots) < n:
        run["notes"][metric] = (f"{len(roots)} {ROOT} spans in the ring for {n} solves"
                                " (no such span, tracing off, or a ring that wrapped)")
        return None
    return roots


def read(run):
    roots = window_solves(run, "pca_fit_host_ms")
    return None if roots is None else sum(r.duration_ns for r in roots) / len(roots) / 1e6
