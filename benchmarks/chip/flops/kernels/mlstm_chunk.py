"""The chunkwise mLSTM forward kernel (``repro.kernels.mlstm_chunk``)."""

import flops

MATCH = ("mlstm_chunk",)


def call(cfg, rc, traffic):
    """The forward's count (:func:`flops.mlstm_chunk`) at the run
    configuration's chunk, where the step has mLSTM layers."""
    if "mlstm" not in {s.kind for s in cfg.pattern}:
        return None
    from repro.models.xlstm import mlstm_dims
    _, nh, p = mlstm_dims(cfg)
    return flops.mlstm_chunk(traffic["batch"], traffic["seq"], nh, p,
                             rc.mlstm_chunk)
