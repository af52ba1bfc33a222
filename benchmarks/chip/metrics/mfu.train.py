"""Model FLOP/s utilization of the train step over the traced window: the
step's model FLOPs (``flops.train_step``: forward and backward, no
recomputation) times the steps completed, over the window and the chip's
bf16 peak, in percent."""


def read(ctx):
    if not ctx.get("steps") or ctx["window_s"] <= 0:
        return None
    achieved = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * achieved / (ctx["peak"]["bf16_flops_per_s"]
                               * ctx["devices"])
