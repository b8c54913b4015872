"""In-place stores a solve that did not take their target's buffer: the
window's ``dispatch.launch`` spans with ``store`` and not ``donated``, a
solve, the store that runs at the caller's read after the last call included
(``scalers_copies`` sums the root spans and cannot see that one).  Expected 0:
each is a second generation of the table alive for the length of a program.
The notes hold the store launches a solve and the deferred stores folded into
them (``folded``, from the chains' own marks)."""

from chipbench.run import load_py


def read(run):
    found = load_py("layer_metrics", "scalers_programs").window_launches(run, "scalers_undonated_stores")
    if found is None:
        return None
    stores = [r for r in found[0] if r.attrs["store"]]
    run["notes"]["scalers_store_launches"] = len(stores) / run["solves"]
    run["notes"]["scalers_folded_stores"] = sum(r.attrs["folded"] for r in stores) / run["solves"]
    return sum(not r.attrs["donated"] for r in stores) / run["solves"]
