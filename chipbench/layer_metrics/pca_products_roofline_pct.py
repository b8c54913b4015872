"""The products' share of their roofline: the least time the chip could take
for the window's products (each reads the table once and makes
``2 rows features ell`` operations: ``work["product_bytes"]`` and
``work["product_operations"]``, from the shapes alone, the larger of the two
over the peaks) over the seconds ``pca_products_ms`` reads.  How many
products a fit makes is the program's own plan: the roots' ``passes`` less
the moments' read."""

from chipbench.run import load_py

MOMENTS_READS = 1  # of a fit's `passes`: the moments'


def read(run):
    s = load_py("layer_metrics", "pca_products_ms").seconds(run, "pca_products_roofline_pct")
    roots = load_py("layer_metrics", "pca_fit_host_ms").window_solves(run, "pca_products_roofline_pct")
    if s is None or roots is None or not run["peaks"] or "product_bytes" not in run["work"]:
        return None
    if any("passes" not in r.attrs for r in roots):
        run["notes"]["pca_products_roofline_pct"] = "a root without `passes`"
        return None
    products = sum(r.attrs["passes"] - MOMENTS_READS for r in roots)
    least = products * max(run["work"]["product_bytes"] / run["peaks"]["hbm_bytes_per_s"],
                           run["work"]["product_operations"] / run["peaks"]["flops_per_s"])
    run["notes"]["pca_products_a_solve"] = products / len(roots)
    return 100.0 * least / s
