"""The counting kernel's share of its roofline: the least time the chip could
take for the window's counting passes (each reads the values and the labels
once: ``work["count_pass_bytes"]``, from the shapes alone, over the memory's
peak; its operations, ``work["count_pass_operations"]``, far under) over the
seconds ``kmedians_count_ms`` reads.  How many passes a solve makes is the
program's own plan, read from its spans: the roots' ``passes`` (reads of the
table a turn, of which the assignment's and the neighbours' are not the
kernel's) times their ``max_iter`` (the cell's ``tol`` lets no turn out)."""

from chipbench.run import load_py

OTHER_READS = 2  # of a turn's `passes`: the assignment's and the neighbours'


def read(run):
    s = load_py("layer_metrics", "kmedians_count_ms").seconds(run, "kmedians_count_roofline_pct")
    pairs = load_py("layer_metrics", "kmedians_loop_enqueue_ms").window_solves(run, "kmedians_count_roofline_pct")
    if s is None or pairs is None or not run["peaks"] or "count_pass_bytes" not in run["work"]:
        return None
    passes = sum((root.attrs["passes"] - OTHER_READS) * root.attrs["max_iter"] for root, _ in pairs)
    least = passes * max(run["work"]["count_pass_bytes"] / run["peaks"]["hbm_bytes_per_s"],
                         run["work"]["count_pass_operations"] / run["peaks"]["flops_per_s"])
    run["notes"]["kmedians_count_passes_a_solve"] = passes / len(pairs)
    return 100.0 * least / s
