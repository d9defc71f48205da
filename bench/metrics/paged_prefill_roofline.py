"""Share of its roofline reached by the chunked paged prefill attention
kernel in the traced stretch: least time of its calls (causal rows at
their positions, the K/V they read) over its device time in the trace."""
KERNEL = r"paged_prefill"


def read(data):
    from bench.harness.flops import least_seconds, paged_prefill_cost
    from bench.harness.trace import matching
    tr = data.trace
    plane = sorted(tr.ops)[0] if tr.ops else None
    ev = matching(tr.ops.get(plane, []), KERNEL, tr.window)
    busy = sum(e.dur for e in ev)
    calls = [(q0, n) for _, q0, n, _, traced in data.window.prefill_calls
             if traced]
    if not ev or not calls or busy <= 0:
        return None
    least = sum(least_seconds(*paged_prefill_cost(
        data.cfg, q0, n, data.kv_dtype, data.act_dtype), data.peak)
        for q0, n in calls)
    return 100.0 * least / busy
