"""Device milliseconds per decode step of the latent-attention expert model:
the executions of the scheduler's decode program in the trace over the
window's decode steps."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.modules:
        return None
    lo, hi = tr.window()
    ev = ctx["costs"].decode_steps_events(tr, lo, hi)
    steps = sum(r.steps for _, r in ctx["window"].waves)
    if not ev or len(ev) != steps:
        return None
    return 1e3 * sum(e - s for _, s, e in ev) * 1e-9 / steps
