"""The q-EI selection's Matérn-5/2 cross-Gram kernel
(``repro.kernels.gp_gram``), [pool, n].  No train step runs it; the tuning
session counts its calls by :func:`flops.gp_gram` (``drivers/tune``,
``gram_calls``)."""

MATCH = ("matern52_cross",)


def call(cfg, rc, traffic):
    return None
