"""Roofline share of the flash-attention forward kernel: the least time
its calls could take (``flops.flash_attention``: causal pairs only, the
larger of the FLOP and byte bounds) over their device time in the trace,
in percent.  The kernel's backward is the jnp VJP and is not counted."""

import flops


def read(ctx):
    durations = ctx["kernels"].get("flash_attention")
    if not durations or "flash_attention" not in ctx["kernel_calls"]:
        return None
    least = flops.roofline_seconds(*ctx["kernel_calls"]["flash_attention"],
                                   ctx["peak"])
    return 100.0 * least * len(durations) / sum(durations)
