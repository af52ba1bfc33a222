"""Roofline share of the Matérn Gram kernel (``gp_gram``): the least time
of its calls — the posterior build's [n, n] Gram and the q-EI selection's
[pool, n] cross-Gram, counted by ``flops.gp_gram`` — over their device
time in the trace, in percent."""

import flops


def read(ctx):
    least, spent = 0.0, 0.0
    for tag, call in (("matern52_gram", "build"), ("matern52_cross",
                                                     "select")):
        durations = ctx["kernels"].get(tag) or []
        least += len(durations) * flops.roofline_seconds(
            *ctx["gram_calls"][call], ctx["peak"])
        spent += sum(durations)
    if spent <= 0:
        return None
    return 100.0 * least / spent
