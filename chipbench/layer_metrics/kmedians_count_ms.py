"""Device time of the counting kernel a solve: the self time of the operation
whose name holds ``kmedians_count`` (the ``name`` of its ``pallas_call``: ONE
call site in the compiled loop, 16 passes a turn) in the reduced device trace,
over the traced solves.  ``kmedians_count_roofline_pct`` reads the same seconds."""


def seconds(run, metric):
    """Seconds the window's counting passes took, or None with the reason in
    ``run["notes"]``."""
    ops = [s for name, s in (run["trace"] or {}).get("top_ops", []) if "kmedians_count" in name]
    if not ops:
        run["notes"][metric] = "no operation named kmedians_count among the trace's top operations"
        return None
    return sum(ops)


def read(run):
    s = seconds(run, "kmedians_count_ms")
    return None if s is None else 1000.0 * s / run["solves"]
