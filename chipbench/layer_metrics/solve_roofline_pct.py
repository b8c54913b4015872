"""Whole-solve share of the roofline: the least time the chip could take for
the traced solves (the larger of bytes / peak bytes/s and operations / peak
FLOP/s, from the driver's work model and peaks.json) over the device's busy
time in the traced window.  Reads the same work whatever implements it."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"] or not run["peaks"]:
        return None
    by_bytes = run["work"]["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    by_ops = run["work"]["operations"] / run["peaks"]["flops_per_s"]
    run["notes"]["roofline_bound"] = "memory" if by_bytes >= by_ops else "compute"
    run["notes"]["least_s_per_solve"] = max(by_bytes, by_ops)
    return 100.0 * run["solves"] * max(by_bytes, by_ops) / trace["busy_s"]
