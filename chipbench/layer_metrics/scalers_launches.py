"""Programs enqueued a solve: the ``launches`` of the window's
``ht.preprocessing.*`` root spans (the steps of ``dispatch.dispatches`` and
``dispatch.external_dispatches`` inside each), summed a solve.  A fit or a
transform that falls apart into more programs shows here."""

from chipbench.run import load_py


def read(run):
    roots = load_py("layer_metrics", "scalers_host_ms").window_roots(run, "scalers_launches")
    if roots is None:
        return None
    return sum(r.attrs["launches"] for r in roots) / run["solves"]
