"""The latent-attention and held-expert counts of ``flops_latent.py``,
checked by hand at Moonlight's published widths."""
import numpy as np
import pytest

from bench.harness import flops_latent as F
from bench.harness.loader import Cell

CFG = Cell("moonlight-serve-plans").config


def test_parameters_held_here_and_whole():
    # 13 layers with 16 of 64 experts held: 2.79e9; all 27 layers with
    # all 64 experts: Moonlight's published 16B
    assert F.param_count(CFG) == pytest.approx(2.789e9, rel=1e-3)
    whole = dict(CFG, num_hidden_layers=27, n_routed_experts=64)
    assert F.param_count(whole) == pytest.approx(15.96e9, rel=1e-3)


def test_latent_decode_cost_by_loops():
    att = [5, 17, 300]
    flops = bytes_ = 0
    for a in att:
        for _ in range(13):                             # layers
            for _ in range(16):                         # heads
                flops += 2 * a * 576                    # q.row over r+rope
                flops += 2 * a * 512                    # p.row over r
            bytes_ += a * 576 * 2                       # rows, once, bf16
            bytes_ += 16 * (576 + 512) * 2              # q in, o out
    f, b = F.latent_decode_cost(CFG, att, "bfloat16", "bfloat16")
    assert (f, b) == (flops, bytes_)


def test_latent_prefill_cost_by_loops():
    q0, n = 64, 20
    flops = 0
    for r in range(n):
        flops += 2 * 16 * (576 + 512) * (q0 + r + 1) * 13
    rows = (q0 + n) * 576 * 2 * 13
    qo = n * 16 * (576 + 512) * 2 * 13
    assert F.latent_prefill_cost(CFG, q0, n, "bfloat16", "bfloat16") == (
        flops, rows + qo)


def test_moe_expert_cost_by_hand():
    # 96 assignments on 14 held experts: 3 matmuls of 2048 x 1408 each
    f, b = F.moe_expert_cost(CFG, 96, 14, "bfloat16", "bfloat16")
    assert f == 96 * 3 * 2 * 2048 * 1408
    assert b == 14 * 3 * 2048 * 1408 * 2 + 96 * 2 * 2048 * 2


def test_token_flops_by_hand():
    d, h, L = 2048, 16, 13
    proj = (d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128
            + h * 128 * d)
    body = 2 * proj * L + 6 * d * 11264 + 12 * (2 * d * 64 + 6 * d * 2816)
    att = np.array([1, 700])
    want = body + 2 * 16 * (576 + 512) * att * L + np.array(
        [2 * d * 163840, 0])
    assert np.allclose(F.token_flops(CFG, att, [True, False]), want)
