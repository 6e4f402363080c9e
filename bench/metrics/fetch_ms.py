"""Host milliseconds per wave inside the scheduler's ``serve.fetch`` span:
bringing every decode step's tokens to the host after the loop.  Nothing is
read unless the trace holds one span per wave of the window."""

SPAN = "serve.fetch"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    spans = T.named(tr.host, SPAN, lo, hi)
    waves = len(ctx["window"].waves)
    if not spans or len(spans) != waves:
        return None
    return 1e3 * sum(e - s for _, s, e in spans) * 1e-9 / waves
