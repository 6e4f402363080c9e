"""Plain reference forward of a Mistral-style decoder over packed weights.

Written from the published equations (Mistral 7B, arXiv:2310.06825, and the
model's ``config.json``), not from the program: RMSNorm with a learned gain,
rotary embedding on the two halves of each head (``rotate_half``), grouped
query attention with a causal mask, SwiGLU feed-forward, pre-norm residuals,
a final RMSNorm and an untied output head.  Sliding-window attention is the
plain causal mask here: every sequence this benchmark sends is shorter than
the 4096-token window, where the two are equal.

Weights are the benchmark's own (``bench.weights``), regenerated from the
seed one layer at a time and dequantized here: a code ``c`` of group ``g``
and column ``n`` means ``(c - zero[g, n]) * scale[g, n]``, and the AWQ scale
divides the activation on its input channel.  Codes sit four to a byte along
the input axis, code ``j`` of byte ``i`` (row ``4 i + j``) in bits
``2j..2j+1``.

``precision="float32"`` computes everything in float32 with full-precision
matmuls.  ``precision="fp8"`` is the control: both operands of every matmul
are rounded to float8 e4m3, scaled per row (activations) or per column
(weights) to the format's largest value, with float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision):
    """x (..., K) @ w (K, N) at the given precision."""
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def unpack_codes(packed, bits: int):
    """(K / per_byte, N) uint8 -> (K, N) int32 codes."""
    per = 8 // bits
    p = packed.astype(jnp.int32)
    fields = [(p >> (bits * j)) & ((1 << bits) - 1) for j in range(per)]
    st = jnp.stack(fields, axis=1)                  # (K/per, per, N)
    return st.reshape(-1, packed.shape[1])


def dequant(leaf: dict, bits: int, group: int):
    codes = unpack_codes(leaf["packed"], bits).astype(jnp.float32)
    scale = jnp.repeat(leaf["scale"].astype(jnp.float32), group, axis=0)
    zero = jnp.repeat(leaf["zero"].astype(jnp.float32), group, axis=0)
    return (codes - zero) * scale


def _linear(x, leaf, bits, group, precision):
    w = dequant(leaf, bits, group)
    return _mm(x / leaf["act_scale"].astype(jnp.float32), w, precision)


def rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * gain.astype(jnp.float32)


def rotary(x, positions, theta):
    """x (B, S, H, D); rotate the halves (x1, x2) of each head by
    position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, precision):
    """Causal grouped-query attention of one sequence.
    q (S,H,D), k/v (S,Hkv,D)."""
    S, H, D = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if precision == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("m", "bits", "group",
                                             "precision"))
def block(x, leaves, *, m, bits, group, precision):
    """One decoder layer over x (B, S, d) float32."""
    m = dict(m)
    B, S, _ = x.shape
    H, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    eps = m["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    lin = functools.partial(_linear, bits=bits, group=group,
                            precision=precision)
    h = rms_norm(x, leaves["ln1"], eps)
    q = lin(h, leaves["wq"]).reshape(B, S, H, hd)
    k = lin(h, leaves["wk"]).reshape(B, S, Hkv, hd)
    v = lin(h, leaves["wv"]).reshape(B, S, Hkv, hd)
    q = rotary(q, pos, m["rope_theta"])
    k = rotary(k, pos, m["rope_theta"])
    # one sequence at a time: the (heads, S, S) scores of a long batch
    # would not fit beside the layer's float32 weights
    o = jax.lax.map(lambda qkv: attention(*qkv, precision), (q, k, v))
    o = o.reshape(B, S, H * hd)
    x = x + lin(o, leaves["wo"])
    h = rms_norm(x, leaves["ln2"], eps)
    a = jax.nn.silu(lin(h, leaves["w_gate"])) * lin(h, leaves["w_up"])
    return x + lin(a, leaves["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(x, ln_f, head, *, eps, precision):
    return _mm(rms_norm(x, ln_f, eps), head.astype(jnp.float32), precision)


def model_key(m: dict) -> tuple:
    """The hashable model sizes the reference and the generator need."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, m[k]) for k in keys)


def logits(seed: int, cfg: dict, tokens: np.ndarray, *,
           precision: str = "float32") -> jax.Array:
    """Logits (B, S, V) float32 of every position of ``tokens`` (B, S),
    computed layer by layer from the seed's weights."""
    m = dict(model_key(cfg))
    q, w = cfg["quant"], cfg["weights"]
    gen = {k: m[k] for k in ("hidden_size", "intermediate_size",
                             "num_attention_heads", "num_key_value_heads",
                             "vocab_size", "head_dim")}
    top = W.top_only(seed, gen, w)
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        del top["embed"]
        for layer in range(m["num_hidden_layers"]):
            leaves = W.one_layer(seed, layer, gen, q, w)
            x = block(x, leaves, m=model_key(cfg), bits=q["bits"],
                      group=q["group_size"], precision=precision)
            del leaves
        return _logits(x, top["ln_f"], top["head"], eps=m["rms_norm_eps"],
                       precision=precision)
