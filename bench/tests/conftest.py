"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

The serving tests drive a run at a tiny size on the CPU (the Pallas
kernels in interpret mode), skipping only the harness's look for a chip.
"""
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench.harness.loader import BENCH, Cell  # noqa: E402

TINY = {
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab_size": 256,
}


#: a pod mix at the tiny size: bursts from pods sharing a preamble, so
#: prefix-shared blocks and the block copy are on the timed path
TINY_POD_MIX = {
    "driver": "serve", "shape_seed": 7, "knee_per_s": 15.0, "load": 0.8,
    "arrivals": {"process": "bursts", "burst_min": 2, "burst_max": 4,
                 "burst_span_s": 0.2},
    "pods": {"count": 2, "preamble_tokens": 32},
    "prompt": {"dist": "uniform", "min": 4, "max": 12},
}


def tiny_cell(name: str, pods: bool = False) -> Cell:
    """A cell of BENCHMARK.json at a size the CPU runs in seconds: the
    model's widths and the traffic's lengths and rate are cut, the
    deployment keeps 4 lanes. ``pods`` swaps in a tiny pod mix."""
    cell = Cell(name)
    cell = copy.copy(cell)
    cell.config = dict(cell.config, **TINY, kv_pool_gib=256 * 1024 / 2 ** 30)
    cell.config["deployment"] = dict(cell.config["deployment"], slots=4)
    t = json.loads(json.dumps(cell.traffic))
    t.update(knee_per_s=15.0)          # the mix keeps its load
    t["prompt"] = {"dist": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 4, "max": 48}
    if pods:
        t.update(copy.deepcopy(TINY_POD_MIX))
    t["output"] = [{"share": 0.5, "min": 2, "max": 4},
                   {"share": 0.5, "min": 6, "max": 10}]
    cell.traffic = t
    cell.limits = dict(cell.limits, sample={"min_served_tokens": 24,
                                            "max_requests": 6})
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
