"""Model FLOPs of the window's decode steps (live slots only; the routed
experts each token uses, the shared experts, absorbed attention over each
request's live length) over the device time of the decode program's
executions, as a share of the chip's bf16 peak."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.modules:
        return None
    c, m = ctx["costs"], ctx["m"]
    lo, hi = tr.window()
    ev = c.decode_steps_events(tr, lo, hi)
    win = ctx["window"]
    if not ev or len(ev) != sum(r.steps for _, r in win.waves):
        return None
    flops = sum(c.decode_flops(m, len(p.prompt), p.max_new_tokens)
                for _, p, _ in win.requests())
    secs = sum(e - s for _, s, e in ev) * 1e-9
    return 100.0 * flops / secs / ctx["peaks"]["bf16_flops"]
