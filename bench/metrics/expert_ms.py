"""Device milliseconds per decode step in the routed experts: the
``quant_gmm_op`` kernel's events inside the decode program's executions
(three a step per expert layer: gate, up, down) over the window's decode
steps.  Nothing is read when the count of events is not three per expert
layer per step."""

KERNEL = "quant_gmm_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops \
            or not tr.modules:
        return None
    m = ctx["m"]
    lo, hi = tr.window()
    ev = ctx["costs"].kernel_in_decode(tr, KERNEL, lo, hi)
    steps = sum(r.steps for _, r in ctx["window"].waves)
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    if not ev or len(ev) != 3 * layers * steps:
        return None
    return 1e3 * sum(e - s for _, s, e in ev) * 1e-9 / steps
