"""A whole run of the latent-attention cell at a tiny size on the CPU,
its control, and a fault the correctness check must catch."""
import copy
import time

import pytest

from bench.harness import cell_run
from bench.harness.loader import Cell

NAME = "moonlight-serve-plans"
SECONDS = 1.5
SEED = 2 ** 36 + 7          # a seed beyond 32 bits
#: Moonlight's keys cut to a size the CPU runs in seconds; 8 experts of
#: which this chip holds 4, one dense and two expert layers. Served in
#: float32: at this size bf16 rounding flips routing ties often enough
#: that the program's widest gap (up to 0.50 over seeds 0-5) passes the
#: float8 control's (0.31 on seed 1), so no limit would tell them apart
TINY = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 8, "n_routed_experts": 4, "expert_offset": 4,
    "num_experts_per_tok": 3, "vocab_size": 256, "dtype": "float32",
}
#: widest and mean logit gap at this tiny size in float32: the program
#: served the reference's top token every time over seeds 0-5 (both 0.0),
#: the float8 control read at least 0.339 and 0.030 (CPU)
TINY_LIMIT = 0.01
TINY_MEAN = 0.001


def tiny_cell() -> Cell:
    cell = copy.copy(Cell(NAME))
    cell.config = dict(cell.config, **TINY,
                       kv_pool_gib=256 * 1024 / 2 ** 30)
    cell.config["deployment"] = dict(cell.config["deployment"], slots=4,
                                     kv_dtype="float32")
    t = copy.deepcopy(cell.traffic)
    t.update(knee_per_s=6.0)
    t["prompt"] = {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 4, "max": 40}
    t["output"] = [{"share": 1.0, "min": 4, "max": 10}]
    cell.traffic = t
    cell.limits = dict(cell.limits, limits={"logit_gap": TINY_LIMIT,
                                            "mean_logit_gap": TINY_MEAN},
                       sample={"min_served_tokens": 24, "max_requests": 6})
    return cell


def tiny_run(patch=None, trace=False, cell=None):
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    return cell_run.run(NAME, SEED, SECONDS, trace, time.perf_counter(),
                        require_chip=False, cell=cell or tiny_cell(),
                        patch=patch, peak=peak)


def test_tiny_run_is_correct():
    result, checks = tiny_run()
    assert result["correct"] is True
    assert result["attempted"] > 3 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in Cell(NAME).end_to_end()}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_traced_tiny_run_reads_the_host_metrics():
    result, _ = tiny_run(trace=True)
    m = result["metrics"]
    # the CPU trace has no TPU plane: device metrics stay silent
    assert "decode_step_ms.latent" not in m
    assert "moe_expert_roofline" not in m
    assert m["compile_s"]["value"] > 0
    # 4 lanes x 3 experts each over 8, 4 of them held: at most 1.5 rows
    assert 0 < m["expert_tokens_per_step"]["value"] <= 1.5
    assert 0 < m["mfu.latent"]["value"]


def test_control_comes_out_not_correct():
    """The float8 reference in the program's place fails the limit the
    program meets, on the prompts and tokens the program served."""
    from bench.harness.compile_log import CompileLog

    cell = tiny_cell()
    clog = CompileLog()
    drv = None
    for seed in (0, 1, 2):
        if drv is None:
            drv = cell.driver().Driver(cell, seed)
        else:
            drv.reseed(seed)
        w = drv.window(drv.work(SECONDS), SECONDS, clog)
        drv.release()
        program, control = drv.check(w), drv.check(w, control=True)
        assert program["logit_gap"]["value"] <= TINY_LIMIT
        assert program["mean_logit_gap"]["value"] <= TINY_MEAN
        assert control["logit_gap"]["value"] > TINY_LIMIT
        assert control["mean_logit_gap"]["value"] > TINY_MEAN


#: one served token in about ``ALTER_ONE_IN`` altered: few enough that
#: the mean gap stays under the cell's limit
ALTER_ONE_IN = 32


def _alter_tokens(drv):
    """A token altered where it is produced: each token the sampler
    emits is, with chance ``1 / ALTER_ONE_IN`` drawn from the step's key,
    the id after the greedy one."""
    import jax
    import jax.numpy as jnp

    make = drv.engine.make_sampler
    v = drv.cfg["vocab_size"]

    def make_sampler(*a, **k):
        inner = make(*a, **k)

        def sample(logits, key):
            t = inner(logits, key)
            hit = jax.random.uniform(key, t.shape) < 1 / ALTER_ONE_IN
            return jnp.where(hit, (t + 1) % v, t)
        return sample
    drv.engine.make_sampler = make_sampler


def test_altered_token_comes_out_not_correct():
    """About one served token in 32 altered, under the cell's own limits
    over some 700 compared tokens: the share of tokens far below the
    reference's best catches it, where the mean alone would not."""
    cell = tiny_cell()
    own = Cell(NAME).limits
    cell.limits = dict(cell.limits, limits=own["limits"],
                       tail_gap=own["tail_gap"],
                       sample={"min_served_tokens": 600,
                               "max_requests": 64})
    cell.config = dict(cell.config, kv_pool_gib=1024 * 1024 / 2 ** 30)
    cell.traffic = dict(cell.traffic,
                        output=[{"share": 1.0, "min": 80, "max": 120}])
    result, checks = tiny_run(patch=_alter_tokens, cell=cell)
    assert result["correct"] is False
    tail = checks["tail_gap_share"]
    assert tail["value"] > tail["limit"]
    assert checks["mean_logit_gap"]["value"] \
        <= checks["mean_logit_gap"]["limit"]
