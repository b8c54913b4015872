"""Device time a fit of the eigendecompositions, the latency-bound part: the
self time of the custom calls whose target is an eigendecomposition (on the
chip ``EighTpu``: the Gram's of every orthonormalization, twice each, and
the one inside the small SVD), over the traced solves.  The reduced trace
keeps only its ten largest operations, so this is what of them is among
those ten: a floor, which leaves out the smaller eigendecompositions and
the small SVD's QR and Cholesky calls (the fit's program holds five
``EighTpu`` sites; three were among the ten in the cell's traces).  Where
none is among them there is nothing to read: nothing, and the reason."""

FACTORIZATIONS = ("eigh",)


def read(run):
    ops = (run["trace"] or {}).get("top_ops", [])
    targets = [(name.split(" custom-call:", 1)[1].split(" ", 1)[0].lower(), s)
               for name, s in ops if " custom-call:" in name]
    found = [s for target, s in targets if any(f in target for f in FACTORIZATIONS)]
    if not found:
        run["notes"]["pca_factor_ms"] = "no eigendecomposition custom call among the trace's top operations"
        return None
    return 1000.0 * sum(found) / run["solves"]
