"""chipbench: the one command of heat_tpu's chip benchmark.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: it makes the cell's data on the device from
``--seed``, warms the cell's own shapes (all of that is ``setup_s``), drives
a closed loop of the cell's public ``ht.*`` entry for ``--seconds``, reads the
peak memory, and only then computes the plain reference and compares what the
last timed solve returned with it.  The last line of standard output is the
one JSON object the driver reads.  Without a TPU, or with another number of
chips than the cell asks for, it exits 2 and prints no result.

This file holds no cell's or configuration's name and no size.  A later PR
adds, and edits nothing that is here:

* a configuration: ``configs/<config>.json`` (sizes, source, precision,
  guarantees, ``limits`` of the numbers compared) and an entry under
  ``configs`` in ``BENCHMARK.json``;
* a traffic mix: ``traffic/<traffic>.json``, the parameters of the one
  generator below, which is a closed loop of one caller: the think time
  between solves, and the length and least solves of a traced window;
* a driver: ``drivers/<driver>.py`` with ``build(cfg, seed, rows)``,
  ``solve(state)``, ``reference(state)``, ``compare(state, out, ref)``,
  ``control(state)`` and ``work(cfg)``; the plain reference lives there and
  imports nothing of the program;
* a cell: ``workloads/<cell>.json`` (config, traffic, chips, driver, why) and
  an entry under ``workloads`` in ``BENCHMARK.json``;
* a per-layer metric: ``layer_metrics/<metric>.py`` with ``read(run)`` that
  returns a number, or ``None`` where it finds nothing to read, and an entry
  under ``per_layer`` in ``BENCHMARK.json``.  ``run`` holds what a traced run
  has to read from: ``trace`` (``trace_reduce.reduce_planes``' result),
  ``solves``, ``window_s``, ``peaks``, ``work`` (the driver's ``work(cfg)``),
  ``compiles_in_window``, and ``notes``, a dict the reader may add to.

``--rehearse`` (not used by the driver) allows the CPU, shrinks the rows to
the configuration's ``rehearse_rows`` and prefixes every metric with
``rehearsal.``: a number from a CPU run never carries a device metric's name.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")  # listed in .gitignore, removed after reading


def load_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_py(kind: str, name: str):
    """Import ``chipbench/<kind>/<name>.py`` by path: names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """Everything a run needs to know about one cell, from its files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if name not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"chipbench: BENCHMARK.json names no workload {name!r}")
    cell = load_json("workloads", name + ".json")

    def listed(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "cfg": load_json("configs", cell["config"] + ".json"),
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "driver": load_py("drivers", cell["driver"]),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


def compile_cache() -> str:
    """The persistent compilation cache where the program keeps it, taking
    every program: PR 21 found 226 of 247 compiles left out for being under
    a second."""
    import jax

    from heat_tpu.core.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return use_compile_cache()


class Counters:
    """Compile requests and persistent-cache hits, by ``jax.monitoring``
    (copied from ``chip_smoke._Counters``)."""

    def __init__(self):
        self.compile_requests = 0
        self.cache_hits = 0

    def install(self) -> None:
        import jax

        def on_event(event, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.compile_requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_listener(on_event)


def run_window(solve, state, seconds: float, traffic: dict, min_solves: int = 1):
    """The one load generator: a closed loop of one caller, who sends the
    next solve ``think_ms`` after the last one's outputs were ready.  Ends
    when the first solve that finishes after ``seconds`` finishes.  Returns
    (last outputs, each solve's seconds, elapsed)."""
    think = traffic["think_ms"] / 1000.0
    times = []
    out = None
    t0 = time.perf_counter()
    while True:
        s = time.perf_counter()
        out = solve(state)  # the previous solve's outputs die here, as in a user's loop
        e = time.perf_counter()
        times.append(e - s)
        if e - t0 >= seconds and len(times) >= min_solves:
            return out, times, e - t0
        if think:
            time.sleep(think)


def window_stats(times, elapsed: float, setup_s: float) -> dict:
    """The end-to-end metrics the harness takes itself, by the host's clock."""
    stats = {"setup_s": setup_s, "solve_ms": 1000.0 * elapsed / len(times)}
    if len(times) >= 20:
        stats["solve_p95_ms"] = 1000.0 * statistics.quantiles(times, n=20)[-1]
    return stats


def judge(numbers: dict, limits: dict):
    """Each number compared beside its limit; a number without a limit, or
    one that is not finite, is not correct."""
    compared, ok = {}, True
    for key, value in numbers.items():
        limit = limits.get(key)
        value = float(value)
        good = limit is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        compared[key] = {"value": value, "limit": limit}
    return ok and bool(compared), compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU at the configuration's rehearse_rows; marks every metric")
    args = ap.parse_args(argv)

    c = load_cell(args.workload)
    cell, cfg, traffic, driver = c["cell"], c["cfg"], c["traffic"], c["driver"]

    import jax

    import heat_tpu  # noqa: F401  the program before the backend: 0.6 s less set-up than after it

    marks = {"import_s": time.perf_counter() - _T0}
    devs = jax.devices()
    marks["reach_device_s"] = time.perf_counter() - _T0
    if not args.rehearse and (devs[0].platform != "tpu" or len(devs) != cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s); JAX reports "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 2
    peaks = load_json("peaks.json").get(devs[0].device_kind)
    if peaks is None and not args.rehearse:
        print(f"chipbench: no peaks for device kind {devs[0].device_kind!r} in peaks.json",
              file=sys.stderr)
        return 2

    cache_dir = compile_cache()
    counters = Counters()
    counters.install()

    rows = cfg["rehearse_rows"] if args.rehearse else None
    work = driver.work(cfg, rows)
    state = driver.build(cfg, args.seed, rows)
    jax.block_until_ready([v.larray_padded for v in state.values() if hasattr(v, "larray_padded")])
    marks["data_ready_s"] = time.perf_counter() - _T0
    for _ in range(2):  # the first compiles (or reads the cache), the second runs warm
        driver.solve(state)
    setup_s = time.perf_counter() - _T0
    setup_compiles, setup_hits = counters.compile_requests, counters.cache_hits

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        seconds, min_solves = min(args.seconds, traffic["traced_seconds"]), traffic["min_traced_solves"]
    else:
        seconds, min_solves = args.seconds, 1
    out, times, elapsed = run_window(driver.solve, state, seconds, traffic, min_solves)
    compiles_in_window = counters.compile_requests - setup_compiles
    if args.trace:
        jax.profiler.stop_trace()

    peak_bytes = max(d.memory_stats()["peak_bytes_in_use"] for d in devs) if devs[0].platform == "tpu" else 0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": peak_bytes}

    stats = window_stats(times, elapsed, setup_s)
    extra = {"rehearsal": True} if args.rehearse else {}
    if args.trace:
        from chipbench import trace_reduce

        reduced = trace_reduce.reduce_dir(TRACE_DIR, elapsed)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=elapsed)
        run = {"trace": reduced, "solves": len(times), "window_s": elapsed, "peaks": peaks,
               "work": work, "compiles_in_window": compiles_in_window, "notes": {}}
        metrics = {}
        for m in c["per_layer"]:
            value = load_py("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra["breakdown"] = {"device_ops": reduced["top_ops"], "idle_gaps": reduced["idle_gaps"]}
        print(json.dumps({"trace_notes": run["notes"], "trace_lines": reduced["lines"]}), flush=True)
    else:
        metrics = {m["name"]: {"value": stats[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"] if m["name"] in stats}

    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "solves": len(times), "elapsed_s": elapsed,
        "window": stats, "setup_marks": marks,
        "solve_ms_min_median_max": [1000 * min(times), 1000 * statistics.median(times), 1000 * max(times)],
        "setup_compile_requests": setup_compiles, "setup_cache_hits": setup_hits,
        "compiles_in_window": compiles_in_window, "cache_dir": cache_dir, "peak_bytes": peak_bytes,
        "work_per_solve": work, "generator": state.get("notes", {}),
    }), flush=True)

    # the reference runs only now: after the window, after the peak was read
    t_ref = time.perf_counter()
    ref = driver.reference(state)
    numbers = driver.compare(state, out, ref)
    correct, compared = judge(numbers, cfg["limits"])
    print(json.dumps({"reference_s": time.perf_counter() - t_ref}), flush=True)

    if args.rehearse:
        metrics = {"rehearsal." + k: v for k, v in metrics.items()}
    result = {"correct": correct, "attempted": len(times), "failed": 0, "metrics": metrics,
              "device": device, **extra, "compared": compared}
    for key, pair in compared.items():
        print(f"compared {key} {pair['value']!r} limit {pair['limit']!r}", file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
