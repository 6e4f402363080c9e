"""Plain reference forward of a DeepSeek-V3-style decoder (Moonlight-16B-A3B)
over packed weights.

Written from the published equations (DeepSeek-V2, arXiv:2405.04434;
DeepSeek-V3, arXiv:2412.19437; the model's ``config.json`` and its
``deepseek_v3`` modeling code), not from the program, in the decompressed
form:

  * attention: ``q = h W_q`` split per head into 128 "nope" and 64 rope
    lanes; ``[c, k_pe] = h W_kv_a``; ``c`` through its own RMSNorm;
    ``[k_nope, v] = c W_kv_b`` per head; rotary embedding on the rope lanes
    of q and on the one ``k_pe`` all heads share, its lanes paired
    ``(2i, 2i + 1)`` (the modeling code views them as ``(d / 2, 2)`` and
    transposes before ``rotate_half``); keys ``[k_nope, k_pe]``; causal
    softmax at scale ``1 / sqrt(192)``; ``o W_o``.
  * the first ``first_k_dense_replace`` layers: SwiGLU of width
    ``intermediate_size``.  The others: ``s = sigmoid(h W_r)``, the top
    ``num_experts_per_tok`` experts of ``s + e_score_correction_bias``,
    weights the chosen ``s`` over their sum (plus 1e-20) times
    ``routed_scaling_factor``; every expert's SwiGLU (width
    ``moe_intermediate_size``) weighted by its weight, zero where it was
    not chosen; plus the shared experts, one SwiGLU of width
    ``n_shared_experts * moe_intermediate_size``.
  * pre-norm residuals, RMSNorm with a learned gain, a final RMSNorm and an
    untied head.

Weights are the benchmark's own (``bench.weights_mla_moe``), regenerated
from the seed one layer at a time and dequantized as ``bench.reference.
dense_lm`` does.  ``precision`` is ``"float32"`` (everything in float32,
full-precision matmuls) or ``"fp8"`` (the control: both operands of every
matmul, the router's included, rounded to float8 e4m3 as in ``dense_lm``).

**Routing given from outside.**  Top-k routing is discontinuous: near a tie
between the k-th and the next expert, rounding at the level of bfloat16
picks the other expert, and with random weights one such flip changes the
rest of the forward enough to flip more in later layers; over 26 expert
layers two correct computations in different precisions end up at
unrelated tokens (PERF.md, Findings).  So :func:`logits` can take the experts
each token was routed to (``experts``, from the program under test) and
use them in place of its own top-k: every other part of the forward, the
expert weights from its own scores included, stays the reference's.  It
returns beside the logits how far each given choice lies below its own:
the k-th largest of its ``s + bias`` minus the smallest of the given
experts' (zero where they are its own top-k), so that the choice itself is
checked too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla_moe as W
from bench.reference.dense_lm import (HIGHEST, _fp8, _linear, _mm,
                                      rms_norm)


def rotary_pairs(x, positions, theta):
    """x (B, S, H, D): dims (2i, 2i + 1) rotated together by position *
    theta^(-2i/D); the result in the order [evens, odds]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], -1)


def attention(q, k, v, scale, precision):
    """Causal multi-head attention of one sequence: q/k (S, H, Dqk), v (S,
    H, Dv)."""
    if precision == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * scale
    S = q.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def _swiglu(h, leaves, lin):
    a = jax.nn.silu(lin(h, leaves["w_gate"])) * lin(h, leaves["w_up"])
    return lin(a, leaves["w_down"])


def _experts(h, leaves, m, lin, precision, given):
    """The routed experts' weighted sum over h (T, d), the experts used (T,
    k) and each token's routing deficit (T,): ``given`` (T, k) experts
    replace the top-k where a row's first entry is not negative."""
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, leaves["router"], precision))
    choice = s + leaves["bias"]
    kth, own = jax.lax.top_k(choice, k)
    idx = jnp.where(given[:, :1] >= 0, given, own)
    deficit = jnp.maximum(kth[:, -1] - jnp.take_along_axis(
        choice, idx, -1).min(-1), 0.0)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m["routed_scaling_factor"]
    gate = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx].add(w)
    act = {n: leaves["experts"][n]["act_scale"] for n in W.FFN}

    def one(acc, xs):
        e_leaves, g = xs
        ex = {n: dict(e_leaves[n], act_scale=act[n]) for n in W.FFN}
        return acc + g[:, None] * _swiglu(h, ex, lin), None

    per_expert = {n: {f: leaves["experts"][n][f]
                      for f in ("packed", "scale", "zero")} for n in W.FFN}
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (per_expert, gate.T))
    return out, idx, deficit


@functools.partial(jax.jit, static_argnames=("m", "bits", "group",
                                             "precision", "dense"))
def block(x, leaves, given, *, m, bits, group, precision, dense):
    """One layer over x (B, S, d) float32; returns (x, experts used (B, S,
    k), routing deficits (B, S)), the last two None for a dense layer.
    ``given`` (B, S, k): experts to route to, -1 where the layer's own
    top-k decides."""
    m = dict(m)
    B, S, d = x.shape
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    eps = m["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    lin = functools.partial(_linear, bits=bits, group=group,
                            precision=precision)
    h = rms_norm(x, leaves["ln1"], eps)
    q = lin(h, leaves["wq"]).reshape(B, S, H, nope + rope)
    kv = lin(h, leaves["wkv_a"])
    c = rms_norm(kv[..., :r], leaves["kv_norm"], eps)
    k_pe = rotary_pairs(kv[..., None, r:], pos, m["rope_theta"])
    kvb = lin(c, leaves["wkv_b"]).reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         rotary_pairs(q[..., nope:], pos, m["rope_theta"])],
                        -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    v = kvb[..., nope:]
    scale = 1.0 / np.sqrt(nope + rope)
    # one sequence at a time: the (heads, S, S) scores of a long batch
    # would not fit beside the layer's float32 weights
    o = jax.lax.map(lambda qkv: attention(*qkv, scale, precision), (q, k, v))
    x = x + lin(o.reshape(B, S, H * dv), leaves["wo"])
    h = rms_norm(x, leaves["ln2"], eps)
    if dense:
        return x + _swiglu(h, leaves, lin), None, None
    h2 = h.reshape(B * S, d)
    f, idx, deficit = _experts(h2, leaves, m, lin, precision,
                               given.reshape(B * S, -1))
    f = f + _swiglu(h2, leaves["shared"], lin)
    return (x + f.reshape(B, S, d), idx.reshape(B, S, -1),
            deficit.reshape(B, S))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(x, ln_f, head, *, eps, precision):
    return _mm(rms_norm(x, ln_f, eps), head.astype(jnp.float32), precision)


def logits(seed: int, cfg: dict, tokens: np.ndarray, *,
           precision: str = "float32", experts=None):
    """(logits (B, S, V) float32 of every position of ``tokens`` (B, S),
    experts used (B, expert layers, S, k), routing deficits (B, expert
    layers, S)), computed layer by layer from the seed's weights.
    ``experts`` (B, expert layers, S, k) int32: the experts to route each
    token to, -1 where the reference's own top-k decides (all of them when
    None)."""
    m = W.sizes(cfg)
    q, w = cfg["quant"], cfg["weights"]
    key = tuple(sorted(m.items()))
    n0, L = m["first_k_dense_replace"], m["num_hidden_layers"]
    B, S = np.asarray(tokens).shape
    k = m["num_experts_per_tok"]
    given = (jnp.full((B, L - n0, S, k), -1, jnp.int32) if experts is None
             else jnp.asarray(experts, jnp.int32))
    top = W.top_only(seed, m, w)
    used, deficits = [], []
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        del top["embed"]
        for layer in range(L):
            leaves = W.one_layer(seed, layer, m, q, w)
            x, idx, deficit = block(
                x, leaves, given[:, max(layer - n0, 0)], m=key,
                bits=q["bits"], group=q["group_size"], precision=precision,
                dense=layer < n0)
            if idx is not None:
                used.append(idx)
                deficits.append(deficit)
            del leaves
        out = _logits(x, top["ln_f"], top["head"], eps=m["rms_norm_eps"],
                      precision=precision)
    return out, jnp.stack(used, 1), jnp.stack(deficits, 1)
