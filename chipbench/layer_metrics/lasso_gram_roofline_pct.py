"""The Gram kernel's share of its roofline: the least time the chip could take
for the window's Gram passes (each reads the table once:
``work["gram_pass_bytes"]``, from the shapes alone, over the memory's peak;
the symmetric product's operations are far under) over the seconds
``lasso_gram_ms`` reads.  One Gram a fit."""

from chipbench.run import load_py


def read(run):
    s = load_py("layer_metrics", "lasso_gram_ms").seconds(run, "lasso_gram_roofline_pct")
    if s is None or not run["peaks"] or "gram_pass_bytes" not in run["work"]:
        return None
    return 100.0 * run["solves"] * run["work"]["gram_pass_bytes"] / run["peaks"]["hbm_bytes_per_s"] / s
