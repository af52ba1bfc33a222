"""Share of the traced tuning window in which no operation ran on the
device (profiler trace: 1 − busy / window), in percent."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
