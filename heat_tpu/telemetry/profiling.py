"""Device-trace hooks (Xprof/perfetto) — the telemetry layer's bridge to
``jax.profiler``.

The reference instruments benchmarks with the external ``perun``
runtime/energy monitor (``@monitor()`` decorators, benchmarks/cb/
linalg.py:4,7); the library itself has no tracing (SURVEY.md §5).  The
TPU-native equivalent is jax.profiler: Xprof/perfetto traces with named
regions so collectives show up attributed to framework ops.  Host-side
structured spans live in :mod:`heat_tpu.telemetry.spans`; this module
starts/stops the *device* trace those spans annotate.

Previously ``heat_tpu.utils.profiling`` (still importable there as a
backward-compatible alias).
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import glob
import os
import re
import time
from typing import Callable, Optional

import jax

__all__ = ["annotate", "exchange_exposure", "idle_by_span", "monitor", "start_trace", "stop_trace", "trace"]

#: what an idle stretch is given to when no program span is open: the
#: caller's own code (its loop, its ``block_until_ready``)
OUTSIDE = "outside ht.*"


def start_trace(log_dir: str) -> None:
    """Begin an Xprof/perfetto trace (analog of starting a perun run)."""
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None):
    """Context manager tracing the enclosed region."""
    if log_dir is None:
        yield
        return
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()


def annotate(name: str):
    """Named trace region; nests into the XLA timeline."""
    return jax.profiler.TraceAnnotation(name)


def monitor(name: Optional[str] = None):
    """Decorator measuring wall time of a benchmark function — the drop-in
    analog of perun's ``@monitor()`` (benchmarks/cb/linalg.py:7).  Blocks on
    the function's jax outputs so async dispatch doesn't hide device time.
    ``last_runtime`` is set even when the wrapped function raises (the
    elapsed time up to the raise), so a failed call can never leave a
    stale measurement from the previous call behind.
    """

    def deco(fn: Callable):
        label = name or fn.__name__

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(label):
                    out = fn(*args, **kwargs)
                    out = jax.block_until_ready(out) if _is_jax_tree(out) else out
                return out
            finally:
                wrapped.last_runtime = time.perf_counter() - t0

        wrapped.last_runtime = None
        return wrapped

    return deco


def _is_jax_tree(x) -> bool:
    leaves = jax.tree_util.tree_leaves(x)
    return any(isinstance(l, jax.Array) for l in leaves)


def _busy(events) -> list:
    """Merged busy intervals [[start, end], ...] of one device's operations
    (a ``while`` holds its body's operations, so they overlap)."""
    merged = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _innermost(spans) -> list:
    """One thread's properly nested spans, flattened: [(start, end, name)]
    sorted and disjoint, each stretch named by the innermost span open in it."""
    segs, stack, cursor = [], [], 0.0  # stack of (name, end)

    def close(until):
        nonlocal cursor
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > cursor:
                segs.append((cursor, end, name))
                cursor = end

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > cursor:
            segs.append((cursor, start, stack[-1][0]))
        cursor = start
        stack.append((name, start + dur))
    close(float("inf"))
    return segs


def attribute_idle(device_ops, thread_spans) -> dict:
    """The pure part of :func:`idle_by_span`, on lists of ``(name,
    start_ns, duration_ns)`` of one clock: ``device_ops`` holds one list a
    device, ``thread_spans`` the program spans of the calling thread.

    The traced span of time runs from the first operation of any device to
    the last one's end.  Each device's idle gaps in it are split where spans
    begin and end, and each stretch goes to the innermost span open in it, or
    to ``outside ht.*``.  Idle seconds add up over the devices."""
    device_ops = [ops for ops in device_ops if ops]
    if not device_ops:
        return {"idle": [], "traced_s": 0.0, "busy_share": 0.0, "longest": None}
    t0 = min(s for ops in device_ops for _, s, _ in ops)
    t1 = max(s + d for ops in device_ops for _, s, d in ops)
    segs = _innermost(thread_spans)
    seg_starts = [seg[0] for seg in segs]
    tally, longest, busy_ns = {}, (0.0, OUTSIDE), 0.0  # tally: name -> [idle ns, gaps]
    for ops in device_ops:
        merged = _busy(ops)
        busy_ns += sum(e - s for s, e in merged)
        edges = [t0] + [t for pair in merged for t in pair] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):  # the gaps: before, between and after
            got = {}
            for s, e, name in segs[max(bisect.bisect_right(seg_starts, a) - 1, 0):]:
                if s >= b:
                    break
                if min(e, b) > max(s, a):
                    got[name] = got.get(name, 0.0) + min(e, b) - max(s, a)
            got[OUTSIDE] = (b - a) - sum(got.values())
            for name, ns in got.items():
                if ns > 0:
                    entry = tally.setdefault(name, [0.0, 0])
                    entry[0] += ns
                    entry[1] += 1
                    longest = max(longest, (ns / 1e9, name))
    return {
        "idle": sorted(((n, ns / 1e9, gaps) for n, (ns, gaps) in tally.items()), key=lambda r: -r[1]),
        "traced_s": (t1 - t0) / 1e9,
        "busy_share": busy_ns / (len(device_ops) * (t1 - t0)),
        "longest": longest,
    }


def _newest_planes(trace_dir: str):
    """The planes of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return ProfileData.from_file(paths[-1]).planes


def idle_by_span(trace_dir: str) -> dict:
    """The device's idle time by what the host was doing, from the newest
    trace under ``trace_dir`` (as :func:`start_trace` / ``jax.profiler``
    leaves it; host tracer level 1 or more keeps the spans' annotations).

    Both planes come from one profiler session and so share its clock: the
    union of the ``XLA Ops`` of each ``/device:TPU:*`` plane is busy time,
    and every idle gap is given to the innermost program span open on the
    calling thread (the ``/host:CPU`` line with most span events), or to
    ``outside ht.*``.  A program span is an event named as a span in this
    process's ring, so call it in the process that was traced.  Returns
    ``{"idle": [(span name, idle seconds, gaps)] largest first, "traced_s",
    "busy_share", "longest": (seconds, span name) of one stretch}``."""
    from .spans import get_spans

    names = {rec.name for rec in get_spans()}
    device_ops, threads = [], []
    for plane in _newest_planes(trace_dir):
        if plane.name.startswith("/device:TPU:"):
            device_ops += [
                [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                for line in plane.lines if line.name == "XLA Ops"
            ]
        elif plane.name == "/host:CPU":
            threads += [
                [(e.name, e.start_ns, e.duration_ns) for e in line.events if e.name in names]
                for line in plane.lines
            ]
    return attribute_idle(device_ops, max(threads, key=len, default=[]))


#: the exchanges between devices, by the stem of their opcode
_EXCHANGES = ("all-to-all", "collective-permute", "all-gather", "all-reduce", "reduce-scatter", "collective-broadcast")
_HLO = re.compile(r"%([^ ]+?)(?:\.\d+)? = .*? ([a-z][a-z\-]*)\(")


def _exchange_part(hlo: str):
    """``(stem, part)`` of an exchange in a device trace, which names an
    operation by its HLO line; ``part`` is ``""`` for a synchronous one,
    ``"start"`` or ``"done"`` for the halves of an asynchronous one, whatever
    its form: ``%x = ... all-to-all-start(`` or, as the TPU's compiler writes
    it, ``%all-to-all-start.4 = ... async-start(``, where only the
    instruction's name says what is started.  None for any other operation."""
    m = _HLO.match(hlo)
    if not m:
        return None
    name, opcode = m.groups()
    what = name if opcode in ("async-start", "async-done") else opcode
    for stem in _EXCHANGES:
        if what == stem:
            return stem, ""
        if what in (stem + "-start", stem + "-done"):
            return stem, what[len(stem) + 1:]
    return None


def _self_times(events):
    """``(name, self ns)`` of each event of one line: its duration less the
    events nested in it (a ``while`` holds its body's operations)."""
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    return out + [tuple(entry[::2]) for entry in stack]


def attribute_exchanges(device_ops, async_ops=()) -> dict:
    """The pure part of :func:`exchange_exposure`, on lists of ``(HLO line,
    start_ns, duration_ns)``: ``device_ops`` holds one list a device (its
    ``XLA Ops``), ``async_ops`` likewise its ``Async XLA Ops``.  Seconds are
    a device's, the mean over the devices, summed over EVERY exchange
    operation of the trace, however small."""
    n = max(len(device_ops), 1)
    sums = {"": 0.0, "start": 0.0, "done": 0.0}
    counts = {"": 0, "start": 0, "done": 0}
    by_stem = {}
    for ops in device_ops:
        for hlo, ns in _self_times(ops):
            part = _exchange_part(hlo)
            if part:
                sums[part[1]] += ns
                counts[part[1]] += 1
                if part[1] != "start":
                    by_stem[part[0]] = by_stem.get(part[0], 0.0) + ns / n / 1e9
    # the profiler fills ``Async XLA Ops`` on some planes only (the first of four v5e chips, PR 32): the mean is theirs
    in_flight = sum(d for ops in async_ops for hlo, _, d in ops if _exchange_part(hlo)) / max(sum(1 for ops in async_ops if ops), 1)
    return {
        "exposed_s": (sums[""] + sums["done"]) / n / 1e9,
        "issue_s": sums["start"] / n / 1e9,
        "in_flight_s": in_flight / 1e9,
        "hidden_s": max(in_flight - sums["done"] / n, 0.0) / 1e9,
        "exposed_by_exchange": by_stem,
        "synchronous": counts[""] // n,
        "asynchronous": counts["start"] // n,
        "devices": len(device_ops),
    }


def exchange_exposure(trace_dir: str) -> dict:
    """How much of the devices' exchanges the compute beside them hides,
    from the newest trace under ``trace_dir``: over every operation of each
    ``/device:TPU:*`` plane whose opcode is an exchange (``all-to-all``,
    ``collective-permute``, ``all-gather``, ``all-reduce``,
    ``reduce-scatter``, ``collective-broadcast``), synchronous or a start /
    done pair.  Seconds a device over the whole trace (divide by the solves
    traced):

    * ``exposed_s``: self time in synchronous exchanges and in the ``-done``
      halves, while the device runs nothing else (``exposed_by_exchange``
      splits it by opcode);
    * ``issue_s``: self time in the ``-start`` halves;
    * ``in_flight_s``: the pairs' intervals on the ``Async XLA Ops`` line,
      from a start to the end of its done, waiting included (the mean over
      the planes that carry the line: on a four-chip v5e host only the
      first does);
    * ``hidden_s``: ``in_flight_s`` less the time in the dones: how long an
      exchange was in flight while the device ran something else;
    * ``synchronous`` / ``asynchronous``: exchanges a device of each form.

    On the benchmark's FFT cell (``ht.fft.fftn`` of 1024^3 over four v5e
    chips) this is the reading PERF.md gives beside ``solve_ms``: the
    benchmark's own readers keep the ten largest operations and match the
    opcode ``all-to-all`` exactly (ROADMAP S1 (h))."""
    device_ops, async_ops = [], []
    for plane in _newest_planes(trace_dir):
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns) for e in line.events] for line in plane.lines}
            device_ops.append(lines.get("XLA Ops", []))
            async_ops.append(lines.get("Async XLA Ops", []))
    return attribute_exchanges(device_ops, async_ops)
