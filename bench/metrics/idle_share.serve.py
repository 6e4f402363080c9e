"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    busy = T.busy_ns(tr.busy_ops(sorted(tr.device_ops)[0]), lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo))
