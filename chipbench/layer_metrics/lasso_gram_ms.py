"""Device time of the Gram kernel a fit: the self time of the operation whose
name holds ``gram_syrk`` (the ``name`` of its ``pallas_call``) in the reduced
device trace, over the traced solves.  ``lasso_gram_roofline_pct`` reads the
same seconds, and ``lasso_cd_us_per_update`` and ``lasso_xla_ms`` find the
fit's kernels through `seconds` too.  The trace keeps the ten largest
operations: a fit of many small operations pushes a kernel out of them, and
then there is nothing to read."""


def seconds(run, metric, kernel="gram_syrk"):
    """Seconds the window's operations named ``kernel`` took, or None with
    the reason in ``run["notes"]``."""
    ops = [s for name, s in (run["trace"] or {}).get("top_ops", []) if kernel in name]
    if not ops:
        run["notes"][metric] = f"no operation named {kernel} among the trace's top operations"
        return None
    return sum(ops)


def read(run):
    s = seconds(run, "lasso_gram_ms")
    return None if s is None else 1000.0 * s / run["solves"]
