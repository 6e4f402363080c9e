"""Model FLOPs of everything the window served (every prefill and decode
step) over the window's wall seconds, as a share of the chip's bf16 peak."""


def read(ctx):
    c, m = ctx["costs"], ctx["m"]
    win = ctx["window"]
    flops = sum(c.prefill_flops(m, len(p.prompt))
                + c.decode_flops(m, len(p.prompt), p.max_new_tokens)
                for _, p, _ in win.requests())
    return 100.0 * flops / win.wall_s / ctx["peaks"]["bf16_flops"]
