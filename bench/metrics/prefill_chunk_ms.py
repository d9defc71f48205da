"""Device time per launch of the chunked-prefill program, from the
trace."""
PROGRAM = r"_prefill_chunk_impl"


def read(data):
    from bench.harness.trace import matching
    tr = data.trace
    plane = sorted(tr.modules)[0] if tr.modules else None
    ev = matching(tr.modules.get(plane, []), PROGRAM, tr.window)
    return 1e3 * sum(e.dur for e in ev) / len(ev) if ev else None
