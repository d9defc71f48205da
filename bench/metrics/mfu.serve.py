"""Model operations of the window's prefill chunks and decode steps over
the wall time of the scheduler steps that launched device work, as a
share of the chips' bf16 peak."""
from bench.harness import serve


def read(data):
    wall = serve.busy_wall_seconds(data.window)
    if wall <= 0:
        return None
    f = serve.model_flops(data.cfg, data.window)
    return 100.0 * (f["prefill"] + f["decode"]) / (
        wall * data.chips * data.peak["bf16_flops"])
