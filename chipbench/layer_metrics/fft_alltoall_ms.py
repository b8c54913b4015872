"""Device time of the pencil's transposes, a solve a chip: the self time of
the operations whose opcode is ``all-to-all`` in the reduced device trace.
On a v5e host the trace prints each as one synchronous operation on the
``XLA Ops`` line, ``%all_to_all.N = f32[...] all-to-all(...)``: one for a
float32 slab and two (the real and the imaginary plane) for a complex64 one,
so a forward ``fftn`` of a real cube shows three.  No start / done pair, no
permute; the layout copies and reshapes on either side of them (which take
the ``all_to_all`` result's name but another opcode) are not counted.
``top_ops`` is summed over the chips: divided by ``work["chips"]``."""


def seconds_a_chip(run, metric):
    """Seconds the window's all-to-alls took on one chip, or None with the
    reason in ``run["notes"]``."""
    ops = [s for name, s in (run["trace"] or {}).get("top_ops", []) if name.split()[1:2] == ["all-to-all"]]
    if not ops:
        run["notes"][metric] = "no all-to-all among the trace's top operations"
        return None
    return sum(ops) / run["work"].get("chips", 1)


def read(run):
    s = seconds_a_chip(run, "fft_alltoall_ms")
    return None if s is None else 1000.0 * s / run["solves"]
