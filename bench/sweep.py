#!/usr/bin/env python3
"""Find a serving cell's knee: run its mix at a ladder of offered rates.

    python3 bench/sweep.py --workload <cell> --rates 0.3,0.4,0.5 --seconds 51 --seed <n>

One process sets up once, then serves each rate's schedule for
``--seconds`` on a fresh scheduler. Per rate it prints one JSON line:
requests offered and finished, tokens per second, TTFT p50/p90, the
backlog (requests due and not yet finished) at each quarter of the
window, and how much longer the TTFT of the last quarter of requests was
than that of the first quarter. The knee is the highest rate whose
backlog does not grow: the backlog at the close at most twice its
largest at the first three quarter points (and at most 2 where that was
0 or 1), and the last quarter's median TTFT within twice the first
quarter's. A rate's drain after the close is
cut at 15 s.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def backlog(recs, t: float) -> int:
    """Requests due by ``t`` whose last token had not reached the host."""
    return sum(1 for r in recs if r.due <= t
               and not (r.done and r.times[-1] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench.harness import cell_run, device
    from bench.harness.compile_log import CompileLog
    from bench.harness.loader import Cell
    from bench.harness.stats import percentile

    cell = Cell(args.workload)
    devices = device.require_chips(cell.chips)
    cell_run.use_compile_cache()
    clog = CompileLog()
    drv = cell.driver().Driver(cell, args.seed,
                               mark=cell_run.marker(T_START, devices))
    for rate in (float(r) for r in args.rates.split(",")):
        reqs = drv.work(args.seconds, rate_per_s=rate)
        w = drv.window(reqs, args.seconds, clog, drain_s=15.0)
        recs = sorted(w.recs.values(), key=lambda r: r.due)
        ttft = [(r.times[0] if r.times else w.end) - r.due for r in recs]
        q = max(1, len(recs) // 4)
        quarters = [backlog(recs, f * args.seconds)
                    for f in (0.25, 0.5, 0.75, 1.0)]
        growth = float(np.median(ttft[-q:]) / max(np.median(ttft[:q]), 1e-9))
        e2e = drv.end_to_end(w)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "finished": sum(r.done for r in recs),
            "backlog_at_quarters": quarters,
            "ttft_growth": growth,
            "grows": (quarters[-1] > 2 * max(1, *quarters[:-1])
                      or growth > 2.0),
            "serve_tokens_per_s": e2e["serve_tokens_per_s"],
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "steps": len(w.step_walls),
            "mean_step_ms": 1e3 * float(np.mean([d for _, d in
                                                 w.step_walls])),
            "drained_s": w.end}), flush=True)
        drv.release()
        drv.fresh()
    return 0


if __name__ == "__main__":
    sys.exit(main())
