"""Share of the chip's bf16 peak that the GP asks of the traced window
reach: the asks' operations (fit, posterior build, q-EI selection, as
``drivers/tune.gp_ask_flops`` counts them) over the asks' wall time and
the peak, in percent."""


def read(ctx):
    if not ctx.get("gp_asks") or ctx["ask_seconds"] <= 0:
        return None
    return 100.0 * ctx["ask_flops"] / (ctx["ask_seconds"]
                                      * ctx["peak"]["bf16_flops_per_s"])
