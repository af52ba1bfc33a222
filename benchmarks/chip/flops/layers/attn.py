"""Softmax attention: QKᵀ and PV, 2·d each per visible query-key pair and
query head (:func:`flops.pairs`, the sliding window of
``repro.models.attention`` included)."""

import flops


def sequence_flops(cfg, spec, batch, seq, causal):
    return 4.0 * batch * cfg.n_heads * cfg.resolved_head_dim * flops.pairs(
        seq, causal, spec.sliding_window)
