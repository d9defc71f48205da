"""The prefill chunks' part of ``mfu.serve``: model operations of the
prompt tokens computed, over the same step wall time and peak."""
from bench.harness import serve


def read(data):
    wall = serve.busy_wall_seconds(data.window)
    if wall <= 0:
        return None
    f = serve.model_flops(data.cfg, data.window)
    return 100.0 * f["prefill"] / (wall * data.chips
                                   * data.peak["bf16_flops"])
