"""Roofline share of latent decode attention (``kernels/decode_attention``
with one 576-wide latent serving as K and V): the least time of the
window's decode attention over the device time of the kernel's events.

Least time: per layer and per live slot-step, the latent rows up to the
slot's length after the step's write read once, the query in and the
output out (all bf16), over the HBM peak, or the FLOPs over the bf16 peak
where those take longer.  Nothing is read when the trace's count of kernel
events is not one per layer per decode step."""

KERNEL = "decode_attention_op"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops:
        return None
    from bench import trace as T
    c, m, pk = ctx["costs"], ctx["m"], ctx["peaks"]
    lo, hi = tr.window()
    ev = T.named(tr.device_ops[sorted(tr.device_ops)[0]], KERNEL, lo, hi)
    win = ctx["window"]
    steps = sum(r.steps for _, r in win.waves)
    if not ev or len(ev) != steps * m["num_hidden_layers"]:
        return None
    least = m["num_hidden_layers"] * sum(
        c.roofline_seconds(c.latent_attn_calls(m, len(p.prompt),
                                               p.max_new_tokens),
                           pk["bf16_flops"], pk["hbm_bytes_s"])
        for _, p, _ in win.requests())
    return 100.0 * least / (sum(e - s for _, s, e in ev) * 1e-9)
