"""A whole run at a tiny size on the CPU, the control, and the faults the
correctness check must catch."""
import time

import numpy as np
import pytest

from bench.harness import cell_run, serve
from bench.harness.compile_log import CompileLog

SECONDS = 1.5
SEED = 2 ** 36 + 5          # a seed beyond 32 bits
#: widest logit gap at this tiny size: the program read at most 0.019 over
#: seeds 0-7 and the float8 control at least 0.068 (CPU, fleet mix)
TINY_LIMIT = 0.05


def tiny_run(tiny, name, patch=None, trace=False, pods=False):
    cell = tiny(name, pods)
    box = {}

    def keep(drv):
        box["drv"] = drv
        if patch is not None:
            patch(drv)
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    result, checks = cell_run.run(name, SEED, SECONDS, trace,
                                  time.perf_counter(), require_chip=False,
                                  cell=cell, patch=keep, peak=peak)
    return result, checks, box["drv"]


@pytest.mark.parametrize("name,pods", [("adllm-serve-fleet", False),
                                       ("adllm-serve-fleet", True),
                                       ("adllm-serve-overload", False)],
                         ids=["fleet", "pods", "overload"])
def test_tiny_run_is_correct_and_compiles_nothing_in_the_window(tiny, name,
                                                               pods):
    result, checks, drv = tiny_run(tiny, name, pods=pods)
    assert result["correct"] is True
    assert result["attempted"] > 5 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in tiny(name).end_to_end()}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    w = drv.last_window
    assert w.compiles == 0
    assert all(r.done for r in w.recs.values())
    if pods:      # prefix-shared blocks: fewer prompt tokens computed
        assert sum(r.computed for r in w.recs.values()) < sum(
            r.prompt_len for r in w.recs.values())


def test_traced_tiny_run_reads_its_host_metrics(tiny):
    result, _, _ = tiny_run(tiny, "adllm-serve-fleet", trace=True)
    m = result["metrics"]
    # the CPU trace has no TPU plane: device metrics stay silent
    assert "decode_step_ms" not in m and "idle_share.serve" not in m
    assert m["compile_s"]["value"] > 0
    assert m["queue_wait_p90_ms"]["value"] >= 0
    assert 0 < m["mfu.prefill"]["value"] < m["mfu.serve"]["value"]
    assert result["device"]["window_s"] > 0


def test_due_time_accounting():
    recs = {
        0: serve.Rec(0, due=0.0, prompt_len=4, max_new=3, shared=0,
                     times=[0.5, 0.6, 0.8], done=True),
        1: serve.Rec(1, due=1.0, prompt_len=4, max_new=2, shared=0,
                     times=[1.2, 2.5], done=True),
        2: serve.Rec(2, due=1.5, prompt_len=4, max_new=2, shared=0),
    }
    w = serve.Window(seconds=2.0, recs=recs, prefill_calls=[],
                     decode_calls=[], step_walls=[], end=3.0, compiles=0)
    e = serve.end_to_end(w)
    # TTFT from the due time: 0.5, 0.2, and the unstarted request waited
    # at least until the drain ended (3.0 - 1.5)
    assert e["ttft_p90_ms"] == pytest.approx(
        1e3 * np.percentile([0.5, 0.2, 1.5], 90))
    assert e["itl_p95_ms"] == pytest.approx(
        1e3 * np.percentile([0.1, 0.2, 1.3], 95))
    # tokens that reached the host inside the 2 s window: 3 + 1
    assert e["serve_tokens_per_s"] == pytest.approx(2.0)


def test_control_comes_out_not_correct(tiny):
    """The float8 reference in the program's place, on the prompts and
    tokens the program served, fails the limit the program meets."""
    cell = tiny("adllm-serve-fleet")
    clog = CompileLog()
    drv = None
    for seed in (0, 1, 2):
        if drv is None:
            drv = cell.driver().Driver(cell, seed)
        else:
            drv.reseed(seed)
        w = drv.window(drv.work(SECONDS), SECONDS, clog)
        drv.release()
        prog = drv.check(w)
        ctrl = drv.check(w, control=True)
        assert prog["logit_gap"]["value"] <= TINY_LIMIT
        assert ctrl["logit_gap"]["value"] > TINY_LIMIT


def _alter_tokens(drv):
    """A token altered where it is produced: the sampler emits the token
    after the greedy one."""
    make = drv.engine.make_sampler
    v = drv.cfg["vocab_size"]

    def make_sampler(*a, **k):
        inner = make(*a, **k)
        return lambda logits, key: (inner(logits, key) + 1) % v
    drv.engine.make_sampler = make_sampler


def _decode_keeps_state(drv):
    """A step that returns its state unchanged: decode hands back the
    pools it was given, so no decoded token's K/V is kept."""
    inner = drv.engine._decode

    def decode(params, pools, *a):
        logits, _ = inner(params, pools, *a)
        return logits, pools
    drv.engine._decode = decode


@pytest.mark.parametrize("fault", [_alter_tokens, _decode_keeps_state])
def test_faults_come_out_not_correct(tiny, fault):
    result, checks, _ = tiny_run(tiny, "adllm-serve-fleet", patch=fault)
    assert result["correct"] is False
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
