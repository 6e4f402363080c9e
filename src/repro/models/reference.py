"""Plain float32 reference forwards, one per family, for comparing the
program with its equations.

Each is the architecture's forward pass in straightforward ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``: no kernels,
no cache, no batching tricks.  Weights come from the program's own
parameter tree; a packed ``QTensor`` linear is dequantized to float32 and
its AWQ input scale divides the activation, exactly the affine map the
packed artifact stands for.

``mla_moe_logits`` follows DeepSeek-V2/V3 (arXiv:2405.04434,
arXiv:2412.19437) as Moonlight-16B-A3B's ``deepseek_v3`` code has it, in the
decompressed form: every head's key and value are decompressed from the
latent, the rope lanes paired ``(2i, 2i + 1)``; the routed experts are a
weighted sum over all experts, each weight zero unless the expert is among
the top ``k`` of ``sigmoid(x W_r) + bias``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.qtensor import QTensor

_HI = jax.lax.Precision.HIGHEST


def _weight(w):
    """(float32 weight (..., K, N), float32 input scale (K,) or None)."""
    if isinstance(w, QTensor):
        a = None if w.act_scale is None else w.act_scale.astype(jnp.float32)
        return w.dequantize(jnp.float32), a
    return jnp.asarray(w, jnp.float32), None


def _linear(x, w):
    w, a = _weight(w)
    if a is not None:
        x = x / a
    return jnp.matmul(x, w, precision=_HI)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * jnp.asarray(g, jnp.float32)


def _rope_pairs(x, theta):
    """x (S, H, D): dims (2i, 2i + 1) of position s rotated by s *
    theta^(-2i/D), returned as [evens, odds]."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = jnp.asarray(np.arange(S)[:, None] * inv[None], jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], -1)


def _swiglu(h, p):
    return _linear(jax.nn.silu(_linear(h, p["w_gate"]))
                   * _linear(h, p["w_up"]), p["w_down"])


def _mla_layer(bp, x, cfg: ModelConfig):
    """One layer of one sequence x (S, d)."""
    m, H, eps = cfg.mla, cfg.num_heads, cfg.norm_eps
    S = x.shape[0]
    nope, rope, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                         m.v_head_dim, m.kv_lora_rank)
    h = _rms(x, bp["ln1"], eps)
    q = _linear(h, bp["wq"]).reshape(S, H, nope + rope)
    kv = _linear(h, bp["wkv_a"])
    c = _rms(kv[:, :r], bp["kv_norm"], eps)
    k_pe = _rope_pairs(kv[:, None, r:], cfg.rope_theta)
    kvb = _linear(c, bp["wkv_b"]).reshape(S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         _rope_pairs(q[..., nope:], cfg.rope_theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / np.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., nope:],
                   precision=_HI)
    x = x + _linear(o.reshape(S, H * dv), bp["wo"])
    h = _rms(x, bp["ln2"], eps)
    if "moe" not in bp:
        return x + _swiglu(h, bp)
    mp, e = bp["moe"], cfg.moe
    s = jax.nn.sigmoid(jnp.matmul(h, jnp.asarray(mp["router"], jnp.float32),
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + jnp.asarray(mp["bias"], jnp.float32), e.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * e.routed_scaling
    gate = jnp.zeros((S, e.num_experts)).at[jnp.arange(S)[:, None], idx].set(w)
    y = 0.0
    for ex in range(e.num_experts):
        one = {n: _expert(mp[n], ex) for n in ("w_gate", "w_up", "w_down")}
        y = y + gate[:, ex:ex + 1] * _swiglu(h, one)
    return x + y + _swiglu(h, mp["shared"])


def _expert(w, e: int):
    """Expert ``e`` of an expert-stacked weight, as a plain float32 array
    with its input scale applied."""
    wf, a = _weight(w)
    wf = wf[e]
    return wf if a is None else wf / a[:, None]


def _layers(stack, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def mla_moe_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """Logits (B, S, V) float32 of a DeepSeek-V3-style model (family
    ``mla_moe``) over tokens (B, S), one sequence at a time."""
    n0 = cfg.moe.dense_layers
    with jax.default_matmul_precision("highest"):
        out = []
        for seq in np.asarray(tokens):
            x = jnp.asarray(params["embed"], jnp.float32)[seq]
            for i in range(cfg.num_layers):
                key, j = (("dense_blocks", i) if i < n0
                          else ("blocks", i - n0))
                x = _mla_layer(_layers(params[key], j), x, cfg)
            x = _rms(x, params["ln_f"], cfg.norm_eps)
            out.append(_linear(x, params["head"]))
        return jnp.stack(out)
