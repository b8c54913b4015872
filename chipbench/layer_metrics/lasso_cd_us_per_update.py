"""What one turn of the descent costs the device: the self time of the
operation whose name holds ``lasso_cd`` (the ``name`` of the descent's
``pallas_call``, ``core/kernels.py::cd_sweeps``) in the reduced device trace,
over the coordinate updates the window's fits make (the roots' ``max_iter``
times ``features + 1``: the cell's ``tol`` lets no sweep out).  The descent
and nothing else: the Gram's pass is ``lasso_gram_ms``, the rest of a fit
``lasso_xla_ms``.  A program whose descent is no such kernel leaves nothing
to read."""

from chipbench.run import load_py


def read(run):
    cd = load_py("layer_metrics", "lasso_gram_ms").seconds(run, "lasso_cd_us_per_update", kernel="lasso_cd")
    pairs = load_py("layer_metrics", "lasso_fit_host_ms").window_solves(run, "lasso_cd_us_per_update")
    if cd is None or pairs is None:
        return None
    updates = sum(root.attrs["max_iter"] * (root.attrs["features"] + 1) for root, _ in pairs)
    run["notes"]["lasso_updates_a_solve"] = updates / len(pairs)
    return 1e6 * cd / updates
