"""Device time of the GP hyperparameter fit (XLA module ``jit__fit``) per
GP ask in the traced window, in milliseconds."""


def read(ctx):
    t = ctx["modules"].get("jit__fit")
    if t is None or not ctx.get("gp_asks"):
        return None
    return 1e3 * t / ctx["gp_asks"]
