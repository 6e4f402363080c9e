"""Device milliseconds per decode step: the executions of the scheduler's
decode program in the trace over the window's decode steps."""

PROGRAM = "jit_sched_decode_step"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.modules:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    ev = T.named(tr.modules[sorted(tr.modules)[0]], PROGRAM, lo, hi)
    steps = sum(r.steps for _, r in ctx["window"].waves)
    if not ev or len(ev) != steps:
        return None
    return 1e3 * sum(e - s for _, s, e in ev) * 1e-9 / steps
