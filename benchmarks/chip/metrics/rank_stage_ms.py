"""Host time of the rank stage (the ``bench.rank`` span: LHS design, its
evaluations and the Lasso ranking) per session, over the rank stages
that completed inside the traced window, in milliseconds."""


def read(ctx):
    spans = ctx["spans"].get("bench.rank")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
