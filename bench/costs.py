"""Operations and bytes that the serving path's work needs, from shapes.

Model FLOPs count a multiply-add as two operations and only useful rows:
the live slots of a decode step, the true prompt length of a prefill.
Kernel bytes count each operand once, as the least a kernel must move:
packed codes, float32 scale and zero, activations in and out.
"""
from __future__ import annotations

from bench.weights import CODES_PER_BYTE, linear_shapes


def block_linear_params(m: dict) -> int:
    return sum(k * n for k, n in linear_shapes(m).values())


def _attn_width(m: dict) -> int:
    return m["num_attention_heads"] * m["head_dim"]


def decode_flops(m: dict, plen: int, budget: int) -> float:
    """Model FLOPs of one request's decode steps: budget - 1 steps, the
    step for output token j (j >= 1) attending over plen + j positions."""
    steps = budget - 1
    L = m["num_hidden_layers"]
    dense = 2.0 * (L * block_linear_params(m)
                   + m["hidden_size"] * m["vocab_size"])
    # sum over j = 1..steps of (plen + j) attended positions
    positions = steps * plen + steps * (steps + 1) / 2
    attn = 4.0 * L * _attn_width(m) * positions
    return dense * steps + attn


def prefill_flops(m: dict, plen: int) -> float:
    """Model FLOPs of one prefill: every linear over the prompt, causal
    attention, and the output head at the last position."""
    L = m["num_hidden_layers"]
    dense = 2.0 * L * block_linear_params(m) * plen
    attn = 4.0 * L * _attn_width(m) * plen * (plen + 1) / 2
    return dense + attn + 2.0 * m["hidden_size"] * m["vocab_size"]


def weight_bytes(K: int, N: int, bits: int, group: int) -> int:
    """Bytes of one packed weight: codes and float32 scale and zero."""
    return K * N // CODES_PER_BYTE[bits] + 2 * 4 * (K // group) * N


def qlinear_bytes(K: int, N: int, rows: int, bits: int, group: int) -> int:
    """Least HBM traffic of one packed matmul: the weight once, bf16
    activations in and out."""
    return weight_bytes(K, N, bits, group) + 2 * rows * K + 2 * rows * N


def qlinear_calls(m: dict, rows: int, bits: int, group: int):
    """(flops, bytes) of each block linear of every layer at ``rows``
    activation rows, as the kernels are called."""
    out = []
    for _ in range(m["num_hidden_layers"]):
        for K, N in linear_shapes(m).values():
            out.append((2.0 * rows * K * N,
                        qlinear_bytes(K, N, rows, bits, group)))
    return out


def roofline_seconds(calls, peak_flops: float, peak_bytes_s: float) -> float:
    """Least time of a list of (flops, bytes) calls: each call bound by the
    larger of its compute and its memory time."""
    return sum(max(f / peak_flops, b / peak_bytes_s) for f, b in calls)
