"""Host time of the API layer a solve: the mean duration of the window's
``ht.fft.fftn`` root spans, the time the call holds its caller while the
device's work is merely queued (the route, the program's enqueue under
``fft.dispatch``, the result's ``DNDarray`` under ``fft.wrap``).  Read from
the program's span ring, the window's solves found as
``dispatch_enqueue_ms.window_solves`` finds them."""

from chipbench.run import load_py

ROOT, DISPATCH = "ht.fft.fftn", "fft.dispatch"


def read(run):
    finder = load_py("layer_metrics", "dispatch_enqueue_ms")  # a module of our own: load_py makes one a call
    finder.ROOT, finder.DISPATCH = ROOT, DISPATCH
    pairs = finder.window_solves(run, "fft_host_ms")
    if pairs is None:
        return None
    return sum(root.duration_ns for root, _ in pairs) / len(pairs) / 1e6
