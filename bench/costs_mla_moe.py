"""Operations and bytes of a DeepSeek-V3-style model's decode work, from
shapes (the latent-attention, expert-layer counterpart of ``costs.py``).

Model FLOPs count a multiply-add as two operations and only useful work: a
decode step of a request runs every linear of the attention in the absorbed
form (``W_UK`` and ``W_UV`` take the place of ``kv_b_proj``), attention
over the request's live length, the dense layer, and per expert layer its
router, ``num_experts_per_tok`` routed experts and the shared experts, then
the output head.  Kernel bytes count each operand once, as the least a
kernel must move.
"""
from __future__ import annotations

from bench.weights import CODES_PER_BYTE
from bench.weights_mla_moe import linear_shapes

BF16_BYTES = 2
F32_BYTES = 4


def _params(shapes: dict) -> int:
    return sum(k * n for k, n in shapes.values())


def token_linear_params(m: dict) -> int:
    """Weights one decode token multiplies through, head included."""
    d, E = m["hidden_size"], m["n_routed_experts"]
    n0, L = m["first_k_dense_replace"], m["num_hidden_layers"]
    dense = _params(linear_shapes(m, True))
    moe = linear_shapes(m, False)
    expert = _params({k: v for k, v in moe.items()
                      if k not in ("experts", "shared")})
    expert += m["num_experts_per_tok"] * _params(moe["experts"])
    expert += _params(moe["shared"]) + d * E
    return n0 * dense + (L - n0) * expert + d * m["vocab_size"]


def latent_width(m: dict) -> int:
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attn_flops(m: dict, n: int) -> float:
    """One layer's absorbed attention of one query over ``n`` positions:
    scores over the latent and rope lanes, the weighted sum over the
    latent."""
    H = m["num_attention_heads"]
    return 2.0 * H * n * (latent_width(m) + m["kv_lora_rank"])


def decode_flops(m: dict, plen: int, budget: int) -> float:
    """Model FLOPs of one request's decode steps: budget - 1 steps, the step
    for output token j (j >= 1) attending over plen + j positions."""
    steps = budget - 1
    positions = steps * plen + steps * (steps + 1) / 2
    return (2.0 * token_linear_params(m) * steps
            + m["num_hidden_layers"] * attn_flops(m, 1) * positions)


def latent_attn_calls(m: dict, plen: int, budget: int):
    """(flops, bytes) in one layer of each decode step of one request: the
    live latent rows read once (K and V are the same rows), the query in
    and the output out, bf16."""
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    D = latent_width(m)
    return [(attn_flops(m, plen + j),
             BF16_BYTES * ((plen + j) * D + H * D + H * r))
            for j in range(1, budget)]


def expert_weight_bytes(K: int, N: int, q: dict) -> int:
    """One expert's packed weight: codes and float32 scale and zero."""
    return (K * N // CODES_PER_BYTE[q["bits"]]
            + 2 * F32_BYTES * (K // q["group_size"]) * N)


def gmm_calls(m: dict, q: dict, touched, rows: float):
    """(flops, bytes) of the grouped expert matmuls of decode steps: for
    each (step, expert layer) ``touched`` experts read, and ``rows`` real
    rows (live slots times experts per token) in and out; one call per
    expert linear.  The AWQ scale is one float32 per input channel."""
    out = []
    shapes = linear_shapes(m, False)["experts"]
    for t in touched:
        for K, N in shapes.values():
            out.append((2.0 * rows * K * N,
                        int(t) * expert_weight_bytes(K, N, q)
                        + F32_BYTES * K
                        + BF16_BYTES * rows * (K + N)))
    return out


def roofline_seconds(calls, peak_flops: float, peak_bytes_s: float) -> float:
    """Least time of a list of (flops, bytes) calls: each call bound by the
    larger of its compute and its memory time."""
    return sum(max(f / peak_flops, b / peak_bytes_s) for f, b in calls)


DECODE_PROGRAM = "jit_sched_decode_step"


def decode_steps_events(tr, lo: float, hi: float):
    """The decode program's executions in [lo, hi] on the first TPU plane."""
    from bench import trace as T
    return T.named(tr.modules[sorted(tr.modules)[0]], DECODE_PROGRAM, lo, hi)


def kernel_in_decode(tr, kernel: str, lo: float, hi: float):
    """Events of ``kernel`` on the first TPU plane that start inside an
    execution of the decode program (the prefill runs the same kernels)."""
    from bench import trace as T
    spans = sorted((s, e) for _, s, e in decode_steps_events(tr, lo, hi))
    ev = T.named(tr.device_ops[sorted(tr.device_ops)[0]], kernel, lo, hi)
    out, i = [], 0
    for n, s, e in sorted(ev, key=lambda x: x[1]):
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        if i < len(spans) and spans[i][0] <= s:
            out.append((n, s, e))
    return out
