"""Readings that the limits of `correct` are set from, in one process.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 --control-seeds 1,2 --fault-seeds 1,2

For each seed: the cell's data, two warm-up solves, then one solve through
the timed path compared with the reference (a lower reading); for the control
seeds also the driver's lower-precision control put in the program's place,
and for the fault seeds one solve through the timed path with each of the
driver's ``faults()`` planted under it (upper readings).  All at the cell's
own size.  One JSON line a seed, on standard output and appended to ``--out``.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time

from chipbench.run import compile_cache, judge, load_cell


@contextlib.contextmanager
def planted(fault):
    """One of a driver's ``faults()`` in place for the time of a solve."""
    module, name, make = fault
    original = getattr(module, name)
    setattr(module, name, make(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def seeds_of(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    c = load_cell(args.workload)
    cfg, driver = c["cfg"], c["driver"]

    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("chipbench.control: needs a TPU", file=sys.stderr)
        return 2
    compile_cache()
    rows = cfg["rehearse_rows"] if args.rehearse else None
    control_seeds, fault_seeds = set(seeds_of(args.control_seeds)), set(seeds_of(args.fault_seeds))
    for seed in seeds_of(args.seeds):
        t0 = time.perf_counter()
        state = driver.build(cfg, seed, rows)
        for _ in range(2):
            driver.solve(state)
        out = driver.solve(state)
        ref = driver.reference(state)
        line = {"workload": args.workload, "seed": seed, "rehearsal": args.rehearse,
                "program": driver.compare(state, out, ref)}
        line["program_correct"] = judge(line["program"], cfg["limits"])[0]
        del out
        if seed in control_seeds:
            line["control"] = driver.compare(state, driver.control(state), ref)
            line["control_correct"] = judge(line["control"], cfg["limits"])[0]
        if seed in fault_seeds:
            for name, fault in driver.faults().items():
                with planted(fault):
                    line["fault_" + name] = driver.compare(state, driver.solve(state), ref)
                line[f"fault_{name}_correct"] = judge(line["fault_" + name], cfg["limits"])[0]
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del state, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
