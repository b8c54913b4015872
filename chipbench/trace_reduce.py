"""From the profiler's xplane to the numbers the per-layer metrics read.

``events_of`` reads the device planes with nothing but JAX
(``jax.profiler.ProfileData``); everything after it works on plain lists of
``(name, start_ns, duration_ns)`` and is checked on the CPU by
``chipbench.selftest`` against a small recorded trace.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"  # one plane a chip
OPS_LINE = "XLA Ops"           # the line that holds the device's operations


def short_name(hlo: str) -> str:
    """The trace names an operation by its whole HLO line: keep the result's
    name, the opcode, a custom call's target and the result's shape."""
    m = re.match(r"(%[^ ]+) = (.*?) ([a-z][a-z\-]*)\(", hlo)
    if not m:
        return hlo[:120]
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)}{':' + target.group(1) if target else ''} {shape}"[:120]


def events_of(xplane_path: str) -> dict:
    """{device plane: {line: [(name, start_ns, duration_ns), ...]}}"""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            planes[plane.name] = {
                line.name: [(short_name(e.name), float(e.start_ns), float(e.duration_ns)) for e in line.events]
                for line in plane.lines
            }
    return planes


def union(events) -> tuple:
    """Busy intervals [(start, end), ...], merged, and their total length."""
    merged = []
    for start, end in sorted((s, s + d) for _, s, d in events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged, sum(e - s for s, e in merged)


def self_times(events) -> dict:
    """Time by operation name, each event's own time without the events
    nested in it (a ``while`` holds its body's operations)."""
    total = {}
    stack = []  # [name, end, own]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            total[done[0]] = total.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for done in stack:
        total[done[0]] = total.get(done[0], 0.0) + done[2]
    return total


def longest_gaps(events, merged, top: int = 10) -> list:
    """The longest idle gaps, named by the operations on either side."""
    ends = {}
    starts = {}
    for name, start, dur in events:
        starts.setdefault(start, name)
        ends[start + dur] = name
    gaps = [(b[0] - a[1], f"after {ends.get(a[1], '?')} / before {starts.get(b[0], '?')}")
            for a, b in zip(merged, merged[1:])]
    by_name = {}
    for length, name in gaps:
        by_name[name] = by_name.get(name, 0.0) + length
    return [[name, ns / 1e9] for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def reduce_planes(planes: dict, window_s: float, top: int = 10) -> dict:
    """Busy seconds averaged over the chips, the operations that took most
    (self) time summed over the chips, and the longest idle gaps of the
    first chip."""
    busy, ops, gaps, lines = [], {}, [], {}
    for name in sorted(planes):
        events = planes[name].get(OPS_LINE, [])
        lines[name] = {line: len(evs) for line, evs in planes[name].items()}
        merged, ns = union(events)
        busy.append(ns / 1e9)
        for op, own in self_times(events).items():
            ops[op] = ops.get(op, 0.0) + own
        if not gaps:
            gaps = longest_gaps(events, merged, top)
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "top_ops": [[op, ns / 1e9] for op, ns in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": gaps,
        "lines": lines,
    }


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"chipbench: the profiler left no xplane under {trace_dir}")
    return reduce_planes(events_of(paths[-1]), window_s)
