"""Device time of the products that read the table, a fit: the self time of
the operations named by the products' results in the reduced device trace
(``work["product_shapes"]``: ``f32[ell,rows]`` for ``X Q`` and
``f32[ell,features]`` for ``Q^T X``, which no other operation of the fit
writes: the sketch's own passes write ``f32[rows,ell]``), over the traced
solves.  The fit's loop names each product once however many turns run, so
both are among the trace's ten largest operations; where either is not,
there is nothing to read.  ``pca_products_roofline_pct`` and
``pca_factor_ms`` read the same seconds."""


def seconds(run, metric, shapes_key="product_shapes"):
    """Seconds the window's operations whose result has one of
    ``work[shapes_key]`` took, or None with the reason in ``run["notes"]``:
    each shape has to be found."""
    shapes = (run.get("work") or {}).get(shapes_key)
    ops = (run["trace"] or {}).get("top_ops", [])
    found = {shape: [s for name, s in ops if name.endswith(" " + shape)] for shape in shapes or ()}
    missing = [shape for shape, times in found.items() if not times]
    if not shapes or missing:
        run["notes"][metric] = f"no operation writing {missing or shapes_key} among the trace's top operations"
        return None
    return sum(sum(times) for times in found.values())


def read(run):
    s = seconds(run, "pca_products_ms")
    return None if s is None else 1000.0 * s / run["solves"]
