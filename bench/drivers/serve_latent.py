"""Open-loop serving of a latent-attention (MLA) model with experts held
here: ``drivers/serve.py`` and ``harness/serve.py`` as they are, with the
pool sized from the latent row and the expert load kept.

``harness/serve.py`` sizes a K/V pool (2 · layers · KV heads · head_dim
per token); a latent pool holds one row per token per layer, the latent
and its rotary key padded to the pool's lane width, which the program
reports (``repro.serve.kvcache.latent_width``). Each decode and
prefill-chunk call's expert load (the program's int32 [L_moe, 3]) is
kept on the device as the call returns and read once the window has
closed, so nothing syncs inside it; the scheduler's registry counters of
the decode steps' load are read at the close too.

The check computes, beside ``drivers/serve.py``'s widest logit gap
(``logit_gap``), the mean gap over the same served tokens
(``mean_logit_gap``) and the share of them whose gap exceeds the cell's
``tail_gap`` (``tail_gap_share``), and compares each that the cell's
limits name. The router picks its experts by a hard top-k, so rounding
that moves a score across a near tie switches an expert and shifts that
token's logits by far more than rounding alone: a few tokens of every
bf16 sample lie well below the reference's best, and the widest gaps of
the program and of the float8 control overlap
(``cells/moonlight-serve-plans.json`` keeps the readings). The mean
weighs every token compared, and there bf16 and float8 stand about ten
times apart; the tail share counts the tokens served far below the
reference's best, which a few wrong tokens raise where they barely move
the mean. All three numbers are logged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import correct, serve
from bench.harness import traffic as T
from bench.harness.loader import BENCH, load_module

_serve = load_module(BENCH / "drivers" / "serve.py")


class LatentServeDriver(serve.ServeDriver):
    """``ServeDriver`` over a latent pool of ``kv_pool_gib``."""

    def __init__(self, cfg: dict, traffic: dict, model_cfg):
        from repro.serve import PagedCacheSpec, PagedEngine
        from repro.serve.kvcache import latent_width

        self.cfg = cfg
        self.traffic = traffic
        self.model_cfg = model_cfg
        dep = cfg["deployment"]
        self.defaults = serve.program_defaults()
        bs = self.defaults["block_size"]
        self.max_tokens = T.max_request_tokens(traffic)
        per_req = -(-self.max_tokens // bs)
        block_bytes = (cfg["num_hidden_layers"] * bs * latent_width(model_cfg)
                       * jnp.dtype(dep["kv_dtype"]).itemsize)
        self.num_blocks = int(cfg["kv_pool_gib"] * 2 ** 30 // block_bytes)
        self.spec = PagedCacheSpec(num_blocks=self.num_blocks,
                                   block_size=bs,
                                   max_blocks_per_req=per_req)
        self.slots = int(dep["slots"])
        self.engine = PagedEngine(self.model_cfg, self.spec,
                                  max_context=per_req * bs,
                                  slots=self.slots)
        self.prefix = bool(dep["prefix_cache"])
        self.sched = None
        self.traced = False
        self._instrument()
        self._keep_load()

    def _keep_load(self) -> None:
        """After each call, keep (program, traced, its expert load)."""
        e = self.engine
        pf, dec = e.prefill_chunk, e.decode
        self.load_calls: list = []

        def prefill_chunk(*a):
            out = pf(*a)
            self.load_calls.append(("prefill", self.traced, e.moe_stats))
            return out

        def decode(*a):
            out = dec(*a)
            self.load_calls.append(("decode", self.traced, e.moe_stats))
            return out

        e.prefill_chunk, e.decode = prefill_chunk, decode

    def warm(self, sched) -> None:
        super().warm(sched)
        self.load_calls.clear()

    def run_window(self, sched, *a, **k):
        self.load_calls.clear()
        w = super().run_window(sched, *a, **k)
        loads = jax.device_get([c[2] for c in self.load_calls])
        self.expert_calls = [(p, traced, np.asarray(s)) for (p, traced, _), s
                             in zip(self.load_calls, loads)]
        self.load_calls.clear()
        reg = sched.metrics
        n = reg.get("serve_moe_held_assignments")
        hit = reg.get("serve_moe_experts_hit")
        self.registry = {
            "held_assignments": n.value() if n is not None else 0.0,
            "layer_steps": hit.stats()["count"] if hit is not None else 0,
            "experts_held": self.model_cfg.moe.held}
        return w


class Driver(_serve.Driver):
    """``drivers/serve.py``'s driver over :class:`LatentServeDriver`."""

    def __init__(self, cell, seed: int, mark=lambda what: None,
                 patch=None):
        self.cell = cell
        self.drv = LatentServeDriver(cell.config, cell.traffic,
                                     cell.program_config())
        _serve._log(self.drv.describe())
        if patch is not None:
            patch(self.drv)
        self.ref = cell.reference()
        mark("engine built")
        self.reseed(seed, mark)
        self.drv.warm(self.sched)
        mark("warmed")

    def check(self, w, control: bool = False) -> dict:
        """The unfinished requests, and each gap the cell's limits name,
        from one reference pass per request compared."""
        lim = self.cell.limits
        picked = correct.sample(list(w.recs.values()), self.seed,
                                lim["sample"]["min_served_tokens"],
                                lim["sample"]["max_requests"])
        out = {"unfinished_requests": {
            "value": sum(not r.done for r in w.recs.values()), "limit": 0}}
        tail = lim.get("tail_gap")
        gaps = {"logit_gap": None, "mean_logit_gap": None,
                "tail_gap_share": None}
        if picked:
            width = max(int(m["max"]) for m in self.cell.traffic["output"])
            g = np.concatenate(correct.gaps(
                self.ref, self.params, self.cell.config, picked,
                self.drv.max_tokens, width, control=control))
            gaps = {"logit_gap": float(g.max()),
                    "mean_logit_gap": float(g.mean()),
                    "tail_gap_share": (None if tail is None
                                       else float(np.mean(g > tail)))}
            _serve._log(f"compared {len(g)} served tokens "
                        f"of {len(picked)} requests (longest: "
                        f"{picked[0].prompt_len} prompt + "
                        f"{len(picked[0].tokens)} served): widest gap "
                        f"{gaps['logit_gap']!r}, mean "
                        f"{gaps['mean_logit_gap']!r}, share over "
                        f"{tail!r} {gaps['tail_gap_share']!r}"
                        + (" (control)" if control else ""))
        for name, value in gaps.items():
            if name in lim["limits"]:
                out[name] = {"value": value, "limit": lim["limits"][name]}
        return out

    def layer_data(self, w) -> dict:
        return dict(super().layer_data(w), expert_calls=self.drv.expert_calls,
                    registry=self.drv.registry)
