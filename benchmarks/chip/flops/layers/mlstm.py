"""The mLSTM recurrence in its recurrent form: the matrix memory's update
and readout, 4·P² per token and head, and the normalizer's, 4·P.  The
chunkwise form the step runs does the same work in another order."""


def sequence_flops(cfg, spec, batch, seq, causal):
    from repro.models.xlstm import mlstm_dims
    _, nh, p = mlstm_dims(cfg)
    return batch * seq * nh * (4.0 * p ** 2 + 4.0 * p)
