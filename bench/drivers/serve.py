"""Open-loop serving: a mix's schedule sent to ``ContinuousScheduler``
over ``PagedEngine`` on the host's wall clock (``harness/serve.py``).

Set-up builds the engine, makes the weights on the device from the seed
and warms every program of the window. The window sends each request
when it is due. ``correct`` compares the served tokens of a sample of
the finished requests with the configuration's plain reference, and
every request due in the window has to finish.
"""
from __future__ import annotations

import gc

import jax

from bench.harness import correct, serve, traffic
from bench.harness.loader import Cell


def _log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Driver:
    """One cell's serving program, set up for ``seed``. ``mark(what)``
    records a set-up phase; ``patch(drv)`` lets a test break the timed
    path underneath before anything runs."""

    def __init__(self, cell: Cell, seed: int, mark=lambda what: None,
                 patch=None):
        self.cell = cell
        self.drv = serve.ServeDriver(cell.config, cell.traffic,
                                     cell.program_config())
        _log(self.drv.describe())
        if patch is not None:
            patch(self.drv)
        self.ref = cell.reference()
        mark("engine built")
        self.reseed(seed, mark)
        self.drv.warm(self.sched)
        mark("warmed")

    # ---- set-up ------------------------------------------------------
    def reseed(self, seed: int, mark=lambda what: None) -> None:
        """Weights and a fresh scheduler for ``seed``."""
        self.seed = seed
        self.params = None
        gc.collect()
        self.params = jax.block_until_ready(
            self.drv.weights(self.ref, seed))
        mark("weights made")
        self.fresh()
        mark("pools made")

    def fresh(self) -> None:
        """A fresh scheduler (and pools) over the same weights."""
        self.sched = self.drv.scheduler(self.params)

    def work(self, seconds: float, rate_per_s=None) -> list:
        return traffic.schedule(self.cell.traffic, seconds, self.seed,
                                self.cell.config["vocab_size"], rate_per_s)

    # ---- the window --------------------------------------------------
    def window(self, reqs: list, seconds: float, compile_log,
               capture=None, drain_s: float = serve.DRAIN_S):
        return self.drv.run_window(self.sched, reqs, seconds, compile_log,
                                   capture, drain_s)

    def release(self) -> None:
        """Drop the scheduler and its pools before the reference runs."""
        self.drv.release()
        self.sched = None
        gc.collect()

    def summary(self, w) -> str:
        lag = max((r.submit - r.due for r in w.recs.values()
                   if r.submit is not None), default=0.0)
        return (f"{len(w.recs)} requests, {len(w.step_walls)} steps with "
                f"device work, {w.compiles} compiles inside the window, "
                f"drained at {w.end:.3f} s, generator at most "
                f"{lag * 1e3:.1f} ms late")

    # ---- what the run reports ----------------------------------------
    def check(self, w, control: bool = False) -> dict:
        """The numbers compared, each with its limit; the program's
        pools must already be released."""
        lim = self.cell.limits
        picked = correct.sample(list(w.recs.values()), self.seed,
                                lim["sample"]["min_served_tokens"],
                                lim["sample"]["max_requests"])
        unfinished = sum(not r.done for r in w.recs.values())
        out = {"unfinished_requests": {"value": unfinished, "limit": 0}}
        gap = None
        if picked:
            width = max(int(m["max"]) for m in self.cell.traffic["output"])
            g = correct.gaps(self.ref, self.params, self.cell.config, picked,
                             self.drv.max_tokens, width, control=control)
            gap = correct.widest(g)
            _log(f"compared {sum(len(x) for x in g)} served tokens of "
                 f"{len(picked)} requests (longest: {picked[0].prompt_len} "
                 f"prompt + {len(picked[0].tokens)} served)")
        out["logit_gap"] = {"value": gap,
                            "limit": lim["limits"]["logit_gap"]}
        return out

    def end_to_end(self, w) -> dict:
        return serve.end_to_end(w)

    def layer_data(self, w) -> dict:
        cfg = self.cell.config
        return {"cfg": cfg, "traffic": self.cell.traffic, "window": w,
                "kv_dtype": cfg["deployment"]["kv_dtype"],
                "act_dtype": cfg["dtype"]}

    def attempted(self, w) -> int:
        return len(w.recs)

    def failed(self, w) -> int:
        return sum(not r.done for r in w.recs.values())
