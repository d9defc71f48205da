"""Host work per scheduler step in the traced stretch, from the
program's own spans: over the steps whose ``scheduler.admit`` span
starts in the traced window, the summed durations of the step's
``scheduler.admit``, ``scheduler.prefill``, ``scheduler.decode`` and
``scheduler.commit`` spans, averaged per step. A step runs from its
``scheduler.admit`` to the next one. None where the trace holds no
``scheduler.admit`` span (a program without the spans)."""
OPEN = "scheduler.admit"
HOST = ("scheduler.admit", "scheduler.prefill", "scheduler.decode",
        "scheduler.commit")
#: every span the program puts inside a step
PHASES = HOST + ("scheduler.tokens",)


def step_seconds(tr):
    """Seconds per program span name in each step whose ``OPEN`` span
    starts in the window, in step order."""
    spans = sorted((e for e in tr.host if e.name in PHASES),
                   key=lambda e: e.start)
    lo, hi = tr.window
    steps, cur = [], None
    for e in spans:
        if e.name == OPEN:
            cur = {} if lo <= e.start < hi else None
            if cur is not None:
                steps.append(cur)
        if cur is not None:
            cur[e.name] = cur.get(e.name, 0.0) + e.dur
    return steps


def mean_ms(tr, names):
    steps = step_seconds(tr)
    if not steps:
        return None
    return 1e3 * sum(sum(s.get(n, 0.0) for n in names)
                     for s in steps) / len(steps)


def read(data):
    return mean_ms(data.trace, HOST)
