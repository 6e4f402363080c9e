"""Roofline share of the packed decode GEMV (``kernels/quant_gemv``): the
least time of every GEMV call the window's decode steps made (each call
bound by the larger of its FLOPs over the bf16 peak and its bytes over the
HBM peak; all slot rows, as the kernel is called) over the device time of
the kernel's events in the trace.  Nothing is read when the trace's count of
kernel events is not one per block linear per layer per decode step."""

KERNEL = "quant_gemv_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops:
        return None
    from bench import trace as T
    c, m, q, pk = ctx["costs"], ctx["m"], ctx["q"], ctx["peaks"]
    lo, hi = tr.window()
    ev = T.named(tr.device_ops[sorted(tr.device_ops)[0]], KERNEL,
                         lo, hi)
    steps = sum(r.steps for _, r in ctx["window"].waves)
    calls = c.qlinear_calls(m, ctx["mix"]["slots"], q["bits"],
                            q["group_size"])
    if not ev or len(ev) != steps * len(calls):
        return None
    least = steps * c.roofline_seconds(calls, pk["bf16_flops"],
                                       pk["hbm_bytes_s"])
    return 100.0 * least / (sum(e - s for _, s, e in ev) * 1e-9)
