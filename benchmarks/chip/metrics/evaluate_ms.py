"""Host time of the evaluator (the ``bench.evaluate`` span around each
evaluator batch call) per evaluation in the traced window, in
milliseconds."""


def read(ctx):
    spans = ctx["spans"].get("bench.evaluate")
    if not spans or not ctx.get("evaluations"):
        return None
    return 1e3 * sum(spans) / ctx["evaluations"]
