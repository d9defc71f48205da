#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control-seeds 1,2,3]

One process sets the cell up once, then for each seed makes that seed's
weights and inputs, runs the window at the cell's own load through the
timed path, and reads the numbers a run compares. For the control seeds
it also reads the control in the program's place on the same inputs.
One JSON line per seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell_run, device
    from bench.harness.compile_log import CompileLog
    from bench.harness.loader import Cell

    cell = Cell(args.workload)
    devices = device.require_chips(cell.chips)
    cell_run.use_compile_cache()
    clog = CompileLog()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    drv = None
    for seed in seeds:
        if drv is None:
            drv = cell.driver().Driver(
                cell, seed, mark=cell_run.marker(T_START, devices))
        else:
            drv.reseed(seed)
        reqs = drv.work(args.seconds)
        w = drv.window(reqs, args.seconds, clog)
        drv.release()
        out = {"seed": seed, "requests": drv.attempted(w)}
        t = time.perf_counter()
        out["program"] = {k: v["value"] for k, v in drv.check(w).items()}
        out["check_s"] = time.perf_counter() - t
        if seed in control:
            out["control"] = {k: v["value"] for k, v in
                              drv.check(w, control=True).items()}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
