"""Roofline share of decode attention (``kernels/decode_attention``): the
least time of the window's decode attention over the device time of the
kernel's events in the trace.

Least time: per layer and per live slot-step, the bytes the call must move
(the K and V rows up to the slot's length after the step's write, the query
in and the output out, all bf16) over the HBM peak, or its FLOPs over the
bf16 peak where those take longer.  A slot-step does about ``G`` (query
heads per KV head) FLOPs a byte, 4 at Mistral's widths against a v5e ridge
near 240, so its bytes bound it; the reader takes the larger bound rather
than assuming that.  Nothing is read when the trace's count of kernel events
is not one per layer per decode step."""

KERNEL = "decode_attention_op"
BF16_BYTES = 2


def slot_steps(m: dict, plen: int, budget: int):
    """(flops, bytes) in one layer of each decode step of one request: the
    step for output token j (j >= 1) attends over plen + j positions."""
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    out = []
    for j in range(1, budget):
        n = plen + j
        out.append((4.0 * hq * d * n,
                    BF16_BYTES * (2 * n * hkv * d + 2 * hq * d)))
    return out


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
        c.roofline_seconds(slot_steps(m, len(p.prompt), p.max_new_tokens),
                           pk["bf16_flops"], pk["hbm_bytes_s"])
        for _, p, _ in win.requests())
    return 100.0 * least / (sum(e - s for _, s, e in ev) * 1e-9)
