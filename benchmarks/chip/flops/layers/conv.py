"""The gated short convolution adds nothing beyond its weights: the
depthwise kernel [W, d] is a weight leaf, counted with the others once a
token (2·W·d, the convolution's products), and the two gates are
elementwise."""


def sequence_flops(cfg, spec, batch, seq, causal):
    return 0.0
