"""Reads of the table one fit makes: the ``passes`` the window's
``ht.regression.Lasso.fit`` roots carry, from the program's own plan (the
Gram's and the moments': 2; ``tests/test_chip_compile.py`` holds the compiled
program to the same number).  The count lives in the program's span, not
here."""

from chipbench.run import load_py


def read(run):
    pairs = load_py("layer_metrics", "lasso_fit_host_ms").window_solves(run, "lasso_passes")
    if pairs is None:
        return None
    counted = [root.attrs["passes"] for root, _ in pairs if "passes" in root.attrs]
    if len(counted) < len(pairs):
        run["notes"]["lasso_passes"] = f"{len(counted)} of {len(pairs)} roots carry `passes`"
        return None
    return sum(counted) / len(counted)
