"""Reads of the table one fit makes: the ``passes`` the window's
``ht.decomposition.PCA.fit`` roots carry, from the program's own plan (the
moments' one and two a turn of the solver's ``iterated_power + 1``: 11 in the
cell; ``tests/test_chip_compile.py`` holds the compiled programs to the same
number).  The count lives in the program's span, not here."""

from chipbench.run import load_py


def read(run):
    roots = load_py("layer_metrics", "pca_fit_host_ms").window_solves(run, "pca_table_reads")
    if roots is None:
        return None
    counted = [r.attrs["passes"] for r in roots if "passes" in r.attrs]
    if len(counted) < len(roots):
        run["notes"]["pca_table_reads"] = f"{len(counted)} of {len(roots)} roots carry `passes`"
        return None
    return sum(counted) / len(counted)
