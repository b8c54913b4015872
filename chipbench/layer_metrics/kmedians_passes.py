"""Reads of the table one iteration of the fit makes, the assignment's
included: the ``passes`` the window's ``ht.cluster.KMedians.fit`` roots carry,
from the program's static plan (``tests/test_chip_compile.py`` holds the
compiled loop to the same number).  The count lives in the program's span,
not here."""

from chipbench.run import load_py


def read(run):
    pairs = load_py("layer_metrics", "kmedians_loop_enqueue_ms").window_solves(run, "kmedians_passes")
    if pairs is None:
        return None
    counted = [root.attrs["passes"] for root, _ in pairs if "passes" in root.attrs]
    if len(counted) < len(pairs):
        run["notes"]["kmedians_passes"] = f"{len(counted)} of {len(pairs)} roots carry `passes`"
        return None
    return sum(counted) / len(counted)
