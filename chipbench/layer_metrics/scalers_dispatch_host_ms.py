"""The dispatch layer's time busy on the host, a solve: the durations of the
window's ``dispatch.launch`` spans, each from where ``core/dispatch.py``
starts to decide (linearize, key, cache lookup, donation proof) to the return
of the enqueue, summed a solve.  Never device time: the span ends before the
observatory's fence.  The part inside the root spans is a part of what
``scalers_host_ms`` reads; the part outside them (the notes hold it) no other
metric times."""

from chipbench.run import load_py


def read(run):
    found = load_py("layer_metrics", "scalers_programs").window_launches(run, "scalers_dispatch_host_ms")
    if found is None:
        return None
    launches = found[0]
    run["notes"]["scalers_dispatch_host_ms_outside_spans"] = (
        sum(r.duration_ns for r in launches if r.depth == 0) / run["solves"] / 1e6)
    return sum(r.duration_ns for r in launches) / run["solves"] / 1e6
