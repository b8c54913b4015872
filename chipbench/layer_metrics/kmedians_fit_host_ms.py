"""Host time of the API layer a fit: the mean duration of the window's
``ht.cluster.KMedians.fit`` root spans, the time ``fit`` holds its caller while
the device's work is merely queued (initial centers, the loop's and the final
assignment's enqueue, wrapping the results).  A host read that creeps into
``fit`` shows here as the whole solve.  Read from the program's span ring, as
``kmedians_loop_enqueue_ms``."""

from chipbench.run import load_py


def read(run):
    pairs = load_py("layer_metrics", "kmedians_loop_enqueue_ms").window_solves(run, "kmedians_fit_host_ms")
    if pairs is None:
        return None
    return sum(root.duration_ns for root, _ in pairs) / len(pairs) / 1e6
