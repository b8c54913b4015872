"""Passes over the table a ``RobustScaler.fit``: the ``passes`` of the
``statistics.quantiles`` spans inside the window's
``ht.preprocessing.RobustScaler.fit`` roots, a fit (a sort counts as one).
The count lives in the program's span, not here."""

import threading

from chipbench.run import load_py

FIT, QUANTILES = "ht.preprocessing.RobustScaler.fit", "statistics.quantiles"


def read(run):
    from heat_tpu.telemetry import get_spans

    roots = load_py("layer_metrics", "scalers_host_ms").window_roots(run, "quantile_passes")
    if roots is None:
        return None
    fits = [r for r in roots if r.name == FIT]
    me = threading.get_ident()
    inner = [q for q in get_spans() if q.thread_id == me and q.name == QUANTILES and "passes" in q.attrs
             and any(f.start_ns <= q.start_ns and q.start_ns + q.duration_ns <= f.start_ns + f.duration_ns for f in fits)]
    if not fits or not inner:
        run["notes"]["quantile_passes"] = f"{len(inner)} {QUANTILES} spans inside {len(fits)} {FIT} roots"
        return None
    return sum(q.attrs["passes"] for q in inner) / len(fits)
