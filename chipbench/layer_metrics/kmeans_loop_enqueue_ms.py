"""Host time of the dispatch layer a fit: the mean duration of the program's
own ``kmeans.loop`` spans (one enqueue of the whole ``_lloyd_loop`` program)
over the window's solves, read from the program's span ring.

The window's solves are found as ``dispatch_enqueue_ms.window_solves`` finds
them, the last ``run["solves"]`` root spans on the calling thread, under the
KMeans fit's span names.  ``kmeans_fit_host_ms`` reads the same solves."""

from chipbench.run import load_py

ROOT, LOOP = "ht.cluster.KMeans.fit", "kmeans.loop"


def window_solves(run, metric):
    """[(root, loop), ...] of the window's fits, or None with the reason in
    ``run["notes"]``: never a number that was not read."""
    finder = load_py("layer_metrics", "dispatch_enqueue_ms")  # a module of our own: load_py makes one a call
    finder.ROOT, finder.DISPATCH = ROOT, LOOP
    return finder.window_solves(run, metric)


def read(run):
    pairs = window_solves(run, "kmeans_loop_enqueue_ms")
    if pairs is None:
        return None
    return sum(loop.duration_ns for _, loop in pairs) / len(pairs) / 1e6
