"""Share of slot-steps that held a live request over the window's decode
steps (``ServeResult.occupancy`` weighted by each wave's steps)."""


def read(ctx):
    waves = ctx["window"].waves
    steps = sum(r.steps for _, r in waves)
    if not steps:
        return None
    return 100.0 * sum(r.occupancy * r.steps for _, r in waves) / steps
