"""Share of the traced window in which no operation ran on the chip:
one less the union of its operation intervals over the window."""


def read(data):
    from bench.harness.trace import mean_busy_seconds
    tr = data.trace
    win = tr.window[1] - tr.window[0]
    if not tr.ops or win <= 0:
        return None
    return 100.0 * (1.0 - mean_busy_seconds(tr) / win)
