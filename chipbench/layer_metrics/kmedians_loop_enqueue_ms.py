"""Host time of the dispatch layer a fit: the mean duration of the program's
own ``kmedians.loop`` spans (one enqueue of the whole fit loop, a
``lax.while_loop`` of one assignment and one grouped selection a turn) over the
window's solves, read from the program's span ring.

The window's solves are found as ``dispatch_enqueue_ms.window_solves`` finds
them, the last ``run["solves"]`` root spans on the calling thread, under the
KMedians fit's span names.  ``kmedians_fit_host_ms`` and ``kmedians_passes``
read the same solves.  A program without these spans (before PR 37) leaves
nothing to read: None, and the reason in the notes."""

from chipbench.run import load_py

ROOT, LOOP = "ht.cluster.KMedians.fit", "kmedians.loop"


def window_solves(run, metric):
    """[(root, loop), ...] of the window's fits, or None with the reason in
    ``run["notes"]``: never a number that was not read."""
    finder = load_py("layer_metrics", "dispatch_enqueue_ms")  # a module of our own: load_py makes one a call
    finder.ROOT, finder.DISPATCH = ROOT, LOOP
    return finder.window_solves(run, metric)


def read(run):
    pairs = window_solves(run, "kmedians_loop_enqueue_ms")
    if pairs is None:
        return None
    return sum(loop.duration_ns for _, loop in pairs) / len(pairs) / 1e6
