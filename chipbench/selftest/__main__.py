"""CPU checks of the benchmark's own yardstick: ``python3 -m chipbench.selftest``.

1. ``trace_reduce`` gives, for the small recorded trace, the busy union, the
   idle share and the top operations worked out by hand (the literals below).
2. Every file that ``BENCHMARK.json`` names exists, and every name and unit
   keeps to the allowed characters.
3. Each driver's ``work(cfg)`` gives the bytes and operations PERF.md states.
4. At rehearsal size, through the harness itself, for every cell of
   ``BENCHMARK.json``: the program comes out correct, the driver's
   lower-precision control put in its place does not, and each of the driver's
   ``faults()`` planted under the timed path makes ``correct`` come out false.

Nothing here is a device number: the runs are rehearsals on the CPU.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys

from chipbench import run, trace_reduce
from chipbench.control import planted

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def close(got, want, rel=1e-9):
    assert abs(got - want) <= rel * abs(want), (got, want)


def check_trace_reduce():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    # two hSVD solves: no nesting, so busy is the sum of the 60 durations;
    # worked out by marking every elementary interval that some event covers
    ev = [tuple(e) for e in rec["hsvd"]["events"]]
    merged, busy = trace_reduce.union(ev)
    close(busy, 44681058.0)
    close(sum(d for _, _, d in ev), 44681058.0)
    close(1 - busy / (merged[-1][1] - merged[0][0]), 0.03654911449790632)
    red = trace_reduce.reduce_planes({"/device:TPU:0": {"XLA Ops": ev}}, window_s=0.046376062)
    close(red["busy_s"], 0.044681058)
    assert [n for n, _ in red["top_ops"][:3]] == [
        "%fusion.2 fusion f32[12582912,15]",
        "%_hsvd_rank_jit.1 custom-call:tpu_custom_call f32[128,128]",
        "%slice_multiply_fusion fusion f32[12582912,10]"], red["top_ops"][:3]
    close(red["top_ops"][0][1], 0.019906894)
    close(red["top_ops"][1][1], 0.019736758)
    # the longest gap is the host's return and re-dispatch between the two solves
    assert red["idle_gaps"][0][0].startswith("after %slice_multiply_fusion"), red["idle_gaps"][0]
    close(red["idle_gaps"][0][1], 1692524e-9)
    # a `while` that holds 30 x 7 operations: its own time is what its body
    # leaves, and the union must not count the body twice
    ev = [tuple(e) for e in rec["while_loop"]["events"]]
    _, busy = trace_reduce.union(ev)
    close(busy, 470368677.0)
    own = trace_reduce.self_times(ev)
    close(own["%fusion.22 fusion (bf16[100000000], s32[100000000])"], 210359711.0)
    close(own["%multiply_reduce_fusion.5 fusion (f32[], f32[8,16])"], 158572799.0)
    loop = next(e for e in ev if e[0].startswith("%while"))
    body = sum(d for n, s, d in ev if loop[1] <= s and s + d <= loop[1] + loop[2] and n != loop[0])
    close(own[loop[0]], loop[2] - body, rel=1e-6)
    assert trace_reduce.short_name(
        '%copy.2 = f32[128,128]{0,1:T(8,128)S(1)} copy(f32[128,128]{1,0:T(8,128)S(1)} %x)') == "%copy.2 copy f32[128,128]"
    # no device plane, as on the CPU: nothing to read, and the readers return nothing
    assert trace_reduce.reduce_planes({}, 1.0)["busy_s"] == 0.0
    for metric in ("solve_roofline_pct", "device_idle_pct"):
        assert run.load_py("layer_metrics", metric).read(
            {"trace": {"busy_s": 0.0}, "peaks": None, "window_s": 1.0, "notes": {}}) is None


def check_files_and_names():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "-m", "chipbench.run"] and bench["paths"] == ["chipbench"]
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(run.ROOT, c["file"])), c
        with open(os.path.join(run.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"], c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs, w
        cell = run.load_json("workloads", w["name"] + ".json")
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        for kind, name in (("traffic", w["traffic"] + ".json"), ("drivers", cell["driver"] + ".py")):
            assert os.path.isfile(os.path.join(run.HERE, kind, name)), (kind, name)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
        assert set(m.get("workloads", [])) <= cells, m
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and os.path.isfile(
            os.path.join(run.HERE, "layer_metrics", m["name"] + ".py")), m
    for dirpath, _, files in os.walk(run.HERE):
        for name in files:
            if "__pycache__" not in dirpath:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(dirpath, name)
    assert "TPU v5 lite" in run.load_json("peaks.json")


def check_work():
    hsvd = run.load_py("drivers", "hsvd_rank").work(run.load_json("configs", "hsvd-tallskinny.json"))
    m, n, k = 12582912, 128, 10
    assert hsvd == {"bytes": m * n * 4 + m * k * 4, "operations": m * n * n + 2 * m * n * k}, hsvd
    assert hsvd["bytes"] == 6945767424


def rehearse(workload: str, seed: int) -> dict:
    """The rest of a run, without the look for a chip; the last line it prints."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", "0", "--rehearse"])
    assert rc == 0, err.getvalue()[-2000:]
    assert "compared " in err.getvalue() and "limit" in err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control_refused(workload: str, seed: int):
    """The driver's lower-precision control, put in the program's place."""
    c = run.load_cell(workload)
    c["driver"].solve = c["driver"].control
    with planted((run, "load_cell", lambda _: lambda name: c)):
        line = rehearse(workload, seed)
    assert line["correct"] is False, (workload, "control", line["compared"])
    print("  control: refused", refused(line))


def refused(line: dict) -> dict:
    return {k: v["value"] for k, v in line["compared"].items() if v["value"] > v["limit"]}


def check_cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for seed, workload in enumerate(cells, start=21):
        print(f" {workload}")
        line = rehearse(workload, seed)
        assert line["correct"] is True and line["rehearsal"] is True, line["compared"]
        assert all(k.startswith("rehearsal.") for k in line["metrics"]), line["metrics"]
        control_refused(workload, seed + 100)
        for name, fault in run.load_cell(workload)["driver"].faults().items():
            with planted(fault):
                line = rehearse(workload, seed + 200)
            assert line["correct"] is False, (workload, name, line["compared"])
            print(f"  fault {name}: refused", refused(line))


def main() -> int:
    for check in (check_trace_reduce, check_files_and_names, check_work, check_cells):
        check()
        print(f"ok {check.__name__}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
