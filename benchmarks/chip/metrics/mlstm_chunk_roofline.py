"""Roofline share of the chunkwise mLSTM forward kernel: the least time
its calls could take (``flops.mlstm_chunk``: the causal half of each chunk, the
larger of the FLOP and byte bounds) over their device time in the trace,
in percent.  The kernel's backward is the jnp VJP and is not counted."""

import flops


def read(ctx):
    durations = ctx["kernels"].get("mlstm_chunk")
    if not durations or "mlstm_chunk" not in ctx["kernel_calls"]:
        return None
    least = flops.roofline_seconds(*ctx["kernel_calls"]["mlstm_chunk"],
                                   ctx["peak"])
    return 100.0 * least * len(durations) / sum(durations)
