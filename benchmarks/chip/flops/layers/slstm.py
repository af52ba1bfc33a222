"""The sLSTM recurrence adds nothing beyond its weights: its recurrent
weight, block-diagonal, is a parameter leaf of its real shape and is
counted with the other weights, once per token."""


def sequence_flops(cfg, spec, batch, seq, causal):
    return 0.0
