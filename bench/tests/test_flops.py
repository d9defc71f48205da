import numpy as np
import pytest

from bench.harness import flops as F
from bench.harness.loader import Cell

CFG = Cell("adllm-serve-fleet").config


def test_block_params_match_the_published_size():
    # 16 layers of 15.7M matmul weights, embedding and head 32.8M each:
    # flad-adllm has about 317M parameters
    blocks = F.block_matmul_params(CFG) * CFG["num_layers"]
    total = blocks + 2 * CFG["vocab_size"] * CFG["d_model"]
    assert blocks == 16 * (1024 * 1024 * 2 + 2 * 1024 * 512 + 3 * 1024 * 4096)
    assert total == pytest.approx(317e6, rel=0.01)


def test_token_flops_by_brute_force():
    L, d, hq, hd, V = 16, 1024, 16, 64, 32000
    att = np.array([1, 10, 500])
    want = [2 * F.block_matmul_params(CFG) * L + 4 * a * hd * hq * L
            + (2 * d * V if h else 0) for a, h in zip(att, [1, 0, 1])]
    got = F.token_flops(CFG, att, [True, False, True])
    assert np.allclose(got, want)


def test_paged_decode_cost_by_loops():
    att = [5, 17, 300]
    flops = bytes_ = 0
    for a in att:
        for _ in range(16):                           # layers
            for _ in range(16):                       # q heads
                flops += 2 * a * 64 + 2 * a * 64      # q.k and p.v
            bytes_ += 2 * 8 * a * 64 * 2              # k and v, bf16
            bytes_ += 2 * 16 * 64 * 2                 # q in, o out
    f, b = F.paged_decode_cost(CFG, att, "bfloat16", "bfloat16")
    assert (f, b) == (flops, bytes_)


def test_paged_prefill_cost_by_loops():
    q0, n = 64, 20
    flops = 0
    for r in range(n):
        flops += 4 * 16 * 64 * (q0 + r + 1) * 16
    kv = 2 * 8 * (q0 + n) * 64 * 2 * 16
    qo = 2 * 16 * n * 64 * 2 * 16
    assert F.paged_prefill_cost(CFG, q0, n, "bfloat16", "bfloat16") == (
        flops, kv + qo)


def test_least_seconds_takes_the_binding_roof():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_seconds(1000.0, 5.0, peak) == 10.0
    assert F.least_seconds(10.0, 50.0, peak) == 5.0
