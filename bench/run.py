#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found
by name from BENCHMARK.json. With ``--trace 0`` the line carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of part
of the window gives its per-layer metrics. JAX must find a TPU with as
many chips as the cell asks for: otherwise the run exits non-zero and
prints no result. The last lines of standard error are the numbers the
correctness check compared, each with its limit; the last line of
standard output is the result as one JSON object.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell_run
    from bench.harness.device import NoChip

    try:
        result, checks = cell_run.run(args.workload, args.seed,
                                      args.seconds, bool(args.trace),
                                      T_START)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    for line in cell_run.check_lines(checks):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
