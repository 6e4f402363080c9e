"""Device milliseconds per admission's prefill: the executions of the
scheduler's B=1 prefill program in the trace over the window's requests."""

PROGRAM = "jit_prefill_step"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.modules:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    ev = T.named(tr.modules[sorted(tr.modules)[0]], PROGRAM, lo, hi)
    n = ctx["window"].attempted
    if not ev or len(ev) != n:
        return None
    return 1e3 * sum(e - s for _, s, e in ev) * 1e-9 / n
