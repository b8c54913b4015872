"""Device time of the Gram kernel a solve: the self time of the operation
whose name holds ``gram_syrk`` (the ``name`` of its ``pallas_call``) in the
reduced device trace, over the traced solves."""


def read(run):
    ops = [s for name, s in (run["trace"] or {}).get("top_ops", []) if "gram_syrk" in name]
    if not ops:
        run["notes"]["gram_syrk_ms"] = "no operation named gram_syrk among the trace's top operations"
        return None
    return 1000.0 * sum(ops) / run["solves"]
