"""Host time of the API layer a solve: the mean self time of the program's
``ht.linalg.hsvd`` root spans over the window's solves, that is the root less
its ``hsvd.dispatch`` child.  ``hsvd.wrap`` stays in: wrapping the results is
API work.  Read from the program's span ring, as ``dispatch_enqueue_ms``."""

from chipbench.run import load_py


def read(run):
    pairs = load_py("layer_metrics", "dispatch_enqueue_ms").window_solves(run, "api_host_ms")
    if pairs is None:
        return None
    return sum(root.duration_ns - d.duration_ns for root, d in pairs) / len(pairs) / 1e6
