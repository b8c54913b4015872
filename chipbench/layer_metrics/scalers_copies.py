"""In-place stores a solve that did not donate: ``stores - donations`` of the
window's ``ht.preprocessing.*`` root spans, summed a solve.  Expected 0: each
one is a second generation of the table alive for the length of a program."""

from chipbench.run import load_py


def read(run):
    roots = load_py("layer_metrics", "scalers_host_ms").window_roots(run, "scalers_copies")
    if roots is None:
        return None
    return sum(r.attrs["stores"] - r.attrs["donations"] for r in roots) / run["solves"]
