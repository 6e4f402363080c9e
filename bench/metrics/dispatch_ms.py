"""Host milliseconds per decode step inside the scheduler's ``serve.decode``
spans: dispatching the step and the loop's bookkeeping around it.  Nothing
is read unless the trace holds one span per decode step of the window."""

SPAN = "serve.decode"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    spans = T.named(tr.host, SPAN, lo, hi)
    steps = sum(r.steps for _, r in ctx["window"].waves)
    if not spans or len(spans) != steps:
        return None
    return 1e3 * sum(e - s for _, s, e in spans) * 1e-9 / steps
