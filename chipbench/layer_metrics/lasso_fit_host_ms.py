"""Host time of the API layer a fit: the mean duration of the window's
``ht.regression.Lasso.fit`` root spans, the time ``fit`` holds its caller while
the device's work is merely queued (the one program's enqueue, wrapping
``theta``).  A host read that creeps into ``fit`` shows here as the whole
solve.  Read from the program's span ring.

The window's solves are found as ``dispatch_enqueue_ms.window_solves`` finds
them, the last ``run["solves"]`` root spans on the calling thread, under the
Lasso fit's span names; ``lasso_passes`` and ``lasso_cd_us_per_update`` read
the same solves.  A program without these spans (before PR 39) leaves nothing
to read: None, and the reason in the notes."""

from chipbench.run import load_py

ROOT, LOOP = "ht.regression.Lasso.fit", "lasso.loop"


def window_solves(run, metric):
    """[(root, loop), ...] of the window's fits, or None with the reason in
    ``run["notes"]``: never a number that was not read."""
    finder = load_py("layer_metrics", "dispatch_enqueue_ms")  # a module of our own: load_py makes one a call
    finder.ROOT, finder.DISPATCH = ROOT, LOOP
    return finder.window_solves(run, metric)


def read(run):
    pairs = window_solves(run, "lasso_fit_host_ms")
    if pairs is None:
        return None
    return sum(root.duration_ns for root, _ in pairs) / len(pairs) / 1e6
