"""Device time of the q-EI selection (XLA module ``jit_select_batch``) per
GP ask in the traced window, in milliseconds."""


def read(ctx):
    t = ctx["modules"].get("jit_select_batch")
    if t is None or not ctx.get("gp_asks"):
        return None
    return 1e3 * t / ctx["gp_asks"]
