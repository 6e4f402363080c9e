"""Roofline share of the packed prefill matmul (``kernels/quant_matmul``):
the least time of every prefill call of the window (true prompt rows) over
the device time of the kernel's events in the trace.  Nothing is read when
the trace's count of kernel events is not one per block linear per layer
per admission."""

KERNEL = "quant_matmul_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops:
        return None
    from bench import trace as T
    c, m, q, pk = ctx["costs"], ctx["m"], ctx["q"], ctx["peaks"]
    lo, hi = tr.window()
    ev = T.named(tr.device_ops[sorted(tr.device_ops)[0]], KERNEL,
                         lo, hi)
    least, n = 0.0, 0
    for _, p, _ in ctx["window"].requests():
        calls = c.qlinear_calls(m, len(p.prompt), q["bits"], q["group_size"])
        least += c.roofline_seconds(calls, pk["bf16_flops"], pk["hbm_bytes_s"])
        n += len(calls)
    if not ev or len(ev) != n:
        return None
    return 100.0 * least / (sum(e - s for _, s, e in ev) * 1e-9)
