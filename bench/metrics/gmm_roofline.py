"""Roofline share of the grouped packed expert matmul (``kernels/quant_gmm``)
in decode steps: the least time of its calls over the device time of its
events inside the decode program's executions.

Least time of a call: the codes, scale and zero of the experts it touched
(the step's ``experts_touched`` counter for that layer) and the AWQ scale,
plus the real rows in and out, over the HBM peak; or the FLOPs of the real
rows over the bf16 peak, whichever is larger.  Real rows are the wave's
mean live slots a step times the experts per token.  Nothing is read
without the counters or when the count of events is not three per expert
layer per step."""

KERNEL = "quant_gmm_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops \
            or not tr.modules:
        return None
    c, m, q, pk = ctx["costs"], ctx["m"], ctx["q"], ctx["peaks"]
    win = ctx["window"]
    lo, hi = tr.window()
    ev = c.kernel_in_decode(tr, KERNEL, lo, hi)
    steps = sum(r.steps for _, r in win.waves)
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    if not ev or len(ev) != 3 * layers * steps:
        return None
    least = 0.0
    for _, r in win.waves:
        touched = getattr(r, "step_counters", {}).get("experts_touched")
        if touched is None or touched.shape != (r.steps, layers):
            return None
        rows = r.occupancy * r.slots * m["num_experts_per_tok"]
        least += c.roofline_seconds(c.gmm_calls(m, q, touched.ravel(), rows),
                                    pk["bf16_flops"], pk["hbm_bytes_s"])
    return 100.0 * least / (sum(e - s for _, s, e in ev) * 1e-9)
