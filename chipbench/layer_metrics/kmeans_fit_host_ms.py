"""Host time of the API layer a fit: the mean duration of the window's
``ht.cluster.KMeans.fit`` root spans, the time ``fit`` holds its caller while
the device's work is merely queued (initial centers, the loop's and the final
assignment's enqueue, wrapping the results).  A host sync that creeps into
``fit`` shows here as the whole solve.  Read from the program's span ring, as
``kmeans_loop_enqueue_ms``."""

from chipbench.run import load_py


def read(run):
    pairs = load_py("layer_metrics", "kmeans_loop_enqueue_ms").window_solves(run, "kmeans_fit_host_ms")
    if pairs is None:
        return None
    return sum(root.duration_ns for root, _ in pairs) / len(pairs) / 1e6
