"""Share of its roofline reached by the held experts' grouped matmul
(``moe_expert_ffn``) in the traced stretch: the least time its calls
could take (per layer of each traced decode and prefill-chunk call, the
larger of the assignments' operations over peak FLOP/s and the bytes of
the held experts that had rows, each read once, with the rows in and
out, over HBM bandwidth) over the kernel's device time in the trace."""
KERNEL = r"moe_expert_ffn"


def read(data):
    from bench.harness.flops import least_seconds
    from bench.harness.flops_latent import moe_expert_cost
    from bench.harness.trace import matching
    calls = getattr(data, "expert_calls", None)
    tr = data.trace
    plane = sorted(tr.ops)[0] if tr.ops else None
    ev = matching(tr.ops.get(plane, []), KERNEL, tr.window)
    busy = sum(e.dur for e in ev)
    layers = [row for _, traced, load in calls or [] if traced
              for row in load]
    if not ev or not layers or busy <= 0:
        return None
    least = sum(least_seconds(*moe_expert_cost(
        data.cfg, int(n), int(hit), data.cfg["dtype"], data.act_dtype),
        data.peak) for n, hit, _ in layers)
    return 100.0 * least / busy
