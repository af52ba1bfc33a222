"""Roofline share of the dropless expert layer's grouped matmuls: the
least time of a whole step's products (``flops/kernels/megablox_gmm.py``:
the forward and both backward products, at the rows the window held where
the driver read them and else at those even routing sends, the larger of
the FLOP and byte bounds) times the steps of the window, over the summed
device time of every call the tag covers, in percent."""

import flops

TAG = "megablox_gmm"


def read(ctx):
    durations = ctx["kernels"].get(TAG)
    if not durations or TAG not in ctx["kernel_calls"] \
            or not ctx.get("steps"):
        return None
    least = flops.roofline_seconds(*ctx["kernel_calls"][TAG], ctx["peak"])
    return 100.0 * least * ctx["steps"] / sum(durations)
