"""Share of its roofline reached by the paged decode kernel over a
latent pool in the traced stretch: the least time its calls could take
(the larger of operations over peak FLOP/s and bytes over HBM bandwidth,
call by call, for the live lanes at their context lengths, each row read
once) over the kernel's device time in the trace."""
KERNEL = r"paged_decode"


def read(data):
    from bench.harness.flops import least_seconds
    from bench.harness.flops_latent import latent_decode_cost
    from bench.harness.trace import matching
    tr = data.trace
    plane = sorted(tr.ops)[0] if tr.ops else None
    ev = matching(tr.ops.get(plane, []), KERNEL, tr.window)
    busy = sum(e.dur for e in ev)
    calls = [att for _, att, traced in data.window.decode_calls if traced]
    if not ev or not calls or busy <= 0:
        return None
    least = sum(least_seconds(*latent_decode_cost(
        data.cfg, att, data.kv_dtype, data.act_dtype), data.peak)
        for att in calls)
    return 100.0 * least / busy
