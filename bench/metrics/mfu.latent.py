"""Model operations of the window's prefill chunks and decode steps over
the wall time of the scheduler steps that launched device work, as a
share of the chips' bf16 peak, for a latent-attention model with experts
held here: the absorbed MLA and the dense parts of every token
(``flops_latent.token_flops``), and the held experts' SwiGLUs for the
assignments each call's expert load reports."""


def read(data):
    import numpy as np

    from bench.harness import flops_latent as F
    from bench.harness import serve
    calls = getattr(data, "expert_calls", None)
    w = data.window
    wall = serve.busy_wall_seconds(w)
    if wall <= 0 or not calls:
        return None
    total = 0.0
    for t, q0, clen, last, _ in w.prefill_calls:
        if t < w.seconds:
            head = np.zeros(clen, bool)
            head[-1] = last
            total += float(F.token_flops(
                data.cfg, q0 + np.arange(1, clen + 1), head).sum())
    for t, att, _ in w.decode_calls:
        if t < w.seconds:
            total += float(F.token_flops(data.cfg, att,
                                         np.ones(len(att), bool)).sum())
    # the calls' loads, in call order; those dispatched after the close
    # are the drain's, as the window's own calls are the first ones
    n_in = (sum(c[0] < w.seconds for c in w.prefill_calls)
            + sum(c[0] < w.seconds for c in w.decode_calls))
    order = sorted([(c[0], "prefill") for c in w.prefill_calls]
                   + [(c[0], "decode") for c in w.decode_calls])
    if [p for _, p in order] != [c[0] for c in calls]:
        return None
    total += F.expert_flops(data.cfg, [int(load[:, 0].sum())
                                       for _, _, load in calls[:n_in]])
    return 100.0 * total / (wall * data.chips * data.peak["bf16_flops"])
