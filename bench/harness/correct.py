"""Is what the timed path served correct?

Once the window has closed and the program's pools are freed, a sample
of the finished requests, drawn from the seed and always holding the
longest one, is run through the plain reference in float32 at
``highest``: each prompt followed by its served tokens, in one pass. For
every served token the reference's logit of that token is compared with
the reference's best logit at the same position; the widest gap over the
sample is the number held to the cell's limit. Greedy decoding with
exact arithmetic gives a gap of 0; rounding in the served precision
gives small gaps where two logits nearly tie.

The control puts the reference itself, computed with float8 matmul
operands, in the program's place: at each position of the same prompts
and tokens it reads the gap of the token the float8 logits put first.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.harness.traffic import rng


def sample(recs: list, seed: int, min_tokens: int, max_requests: int):
    """Finished requests to compare: the longest (prompt and output),
    then others in an order drawn from the seed, until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    done = [r for r in recs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    order = rng(seed ^ 0x5EED).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for j in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[j])
        n += len(rest[j].tokens)
    return out


def _rows(prompt, tokens, bucket: int, width: int):
    """Token row (prompt, served tokens but the last, zero padding) and
    the positions whose next-token logits chose each served token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    if len(seq) > bucket or len(tokens) > width:
        raise ValueError(f"sequence of {len(seq)} tokens / {len(tokens)} "
                         f"served does not fit {bucket} / {width}")
    row = np.zeros(bucket, np.int32)
    row[:len(seq)] = seq
    pos = len(prompt) - 1 + np.arange(len(tokens))
    rows = np.full(width, pos[-1], np.int32)
    rows[:len(tokens)] = pos
    return row, rows


def gaps(ref, params, cfg: dict, picked: list, bucket: int, width: int,
         control: bool = False) -> List[np.ndarray]:
    """Per request, the reference's best logit less its logit of the
    served token (or, with ``control``, of the float8 reference's top
    token) at every served position."""
    import jax.numpy as jnp

    out = []
    for r in picked:
        row, rows = _rows(r.prompt, r.tokens, bucket, width)
        want = ref.logits_at(params, cfg, row, rows, mode="f32")
        n = len(r.tokens)
        if control:
            low = ref.logits_at(params, cfg, row, rows, mode="fp8")
            chosen = jnp.argmax(low, axis=-1)
        else:
            chosen = jnp.asarray(np.resize(np.asarray(r.tokens, np.int32),
                                           width))
        g = want.max(-1) - jnp.take_along_axis(want, chosen[:, None],
                                               axis=-1)[:, 0]
        out.append(np.asarray(g)[:n])
    return out


def widest(per_request: List[np.ndarray]) -> float:
    return float(max(g.max() for g in per_request))
