"""The scheduler-layer readers over the program's own step spans
(``scheduler.admit|prefill|decode|tokens|commit``), on a hand-made trace
of three steps in the window (one with two token reads), one before it
and one after it."""
import json
import types
from pathlib import Path

import pytest

from bench.harness import trace as T
from bench.harness.loader import metric_reader

DATA = Path(__file__).with_name("data")


def _data(name):
    tr = T.Trace.from_json(json.loads((DATA / name).read_text()))
    return types.SimpleNamespace(trace=tr)


@pytest.fixture
def spans():
    return _data("trace_program_spans.json")


#: (admit + prefill + decode + commit, tokens) per step in the window, ms
STEPS = [(2 + 1 + 1 + 3 + 2, 27 + 86), (1 + 4 + 3, 85), (2 + 3 + 5 + 10, 90)]


@pytest.mark.parametrize("cell", ["serve", "throughput"])
def test_host_step_ms(spans, cell):
    want = sum(h for h, _ in STEPS) / len(STEPS)
    got = metric_reader(f"host_step_ms.{cell}").read(spans)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("cell", ["serve", "throughput"])
def test_token_wait_ms(spans, cell):
    want = sum(t for _, t in STEPS) / len(STEPS)
    got = metric_reader(f"token_wait_ms.{cell}").read(spans)
    assert got == pytest.approx(want)


def test_steps_counted_where_admit_starts_in_the_window(spans):
    steps = metric_reader("host_step_ms.serve").step_seconds(spans.trace)
    assert len(steps) == len(STEPS)
    # the step before the window and the one after it are left out;
    # the last step's spans past the window's end are kept whole
    assert [s["scheduler.tokens"] * 1e3 for s in steps] == pytest.approx(
        [t for _, t in STEPS])
    assert "scheduler.prefill" not in steps[1]


@pytest.mark.parametrize("name", ["host_step_ms.serve", "token_wait_ms.serve",
                                  "host_step_ms.throughput",
                                  "token_wait_ms.throughput"])
def test_silent_without_program_spans(name):
    # the benchmark's own scheduler.step spans are not the program's
    assert metric_reader(name).read(_data("trace_small.json")) is None


def test_traced_tiny_run_reads_the_program_spans(tiny):
    """A traced run at the tiny size on the CPU: both readers of the
    overload cell read, and the program's spans cover the benchmark's
    own ``scheduler.step`` spans but for a few percent of glue."""
    import time

    from bench.harness import cell_run

    name = "adllm-serve-overload"
    result, _ = cell_run.run(name, 2 ** 35 + 11, 1.5, True,
                             time.perf_counter(), require_chip=False,
                             cell=tiny(name),
                             peak={"bf16_flops": 1e12,
                                   "hbm_bytes_per_s": 1e11})
    m = result["metrics"]
    host = m["host_step_ms.throughput"]["value"]
    wait = m["token_wait_ms.throughput"]["value"]
    assert host > 0 and wait > 0
    tr = T.Capture(cell_run.OUT_DIR / name / "trace").load()
    lo, hi = tr.window
    steps = [e.dur for e in tr.host
             if e.name == "scheduler.step" and lo <= e.start < hi]
    mean_ms = 1e3 * sum(steps) / len(steps)
    assert 0.9 * mean_ms <= host + wait <= 1.01 * mean_ms
