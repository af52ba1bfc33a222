"""The Mamba-2 SSD recurrence in its recurrent form: the state's update
and readout, 4·N·P per token and head, with N, P and the head count from
``repro.models.ssm.dims``.  The chunked form the step runs does the same
work in another order; the projections and the convolution are
weights."""


def sequence_flops(cfg, spec, batch, seq, causal):
    from repro.models import ssm
    di, nh, n = ssm.dims(cfg)
    return batch * seq * nh * 4.0 * n * (di // nh)
