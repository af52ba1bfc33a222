"""The GP posterior's Matérn-5/2 Gram kernel (``repro.kernels.gp_gram``),
[n, n].  No train step runs it; the tuning session counts its calls by
:func:`flops.gp_gram` (``drivers/tune``, ``gram_calls``)."""

MATCH = ("matern52_gram",)


def call(cfg, rc, traffic):
    return None
