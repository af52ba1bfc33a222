"""Roofline share of the flash-attention kernels: the forward's least
time (``flops.flash_attention``: causal pairs only, the larger of the FLOP
and byte bounds) for every call the tag covers, over their device time in
the trace, in percent.  The tag covers a step's forward and its two
backward kernels, ``flash_attention_dkdv`` and ``flash_attention_dq``
(``flops/kernels/flash_attention.py``); the backward calls do more than
the forward's products, so the share stays under 100 %."""

import flops


def read(ctx):
    durations = ctx["kernels"].get("flash_attention")
    if not durations or "flash_attention" not in ctx["kernel_calls"]:
        return None
    least = flops.roofline_seconds(*ctx["kernel_calls"]["flash_attention"],
                                   ctx["peak"])
    return 100.0 * least * len(durations) / sum(durations)
