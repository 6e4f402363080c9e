"""The packed serving artifact of a DeepSeek-V3-style model (latent attention,
a leading dense layer, expert layers with shared experts), made on the
device from a seed.

The tree has exactly the structure ``repro.core.pack_model`` returns for the
program's ``mla_moe`` family quantized with AWQ: bf16 ``embed``/``head``,
bf16 norm gains, a float32 router and correction bias per expert layer, and
one stacked ``QTensor`` per linear (uint8 codes packed along the input axis,
float32 scale and zero per group, float32 AWQ ``act_scale`` per input
channel, one for all of a layer's experts).  ``dense_blocks`` holds the
leading dense layers, ``blocks`` the expert layers.  Codes are uniform
random; everything else is random in the ranges the configuration file
states and, where the program reads it in bf16, rounded to a bfloat16
value, so that the program and the float32 reference see the same numbers.

Every leaf of layer ``l`` (0-based over all layers) comes from its own key,
``fold_in(layer_key, l)``, so the reference regenerates one layer at a time
(:func:`one_layer`) and gets the same bits as the stacked tree made in one
jitted call (:func:`make_packed_params`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import (CODES_PER_BYTE, _bf16_round, _frozen, _uniform,
                           keys, layer_key)

ATTN = ("wq", "wkv_a", "wkv_b", "wo")
FFN = ("w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    """The model sizes the generator and the reference read, under the
    names of the model's ``config.json``."""
    names = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_attention_heads", "num_hidden_layers", "vocab_size",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_routed_experts", "n_shared_experts",
             "num_experts_per_tok", "first_k_dense_replace",
             "routed_scaling_factor", "rms_norm_eps", "rope_theta")
    return {k: cfg[k] for k in names}


def linear_shapes(m: dict, dense: bool) -> dict:
    """(in_features, out_features) of each linear of a dense or an expert
    layer; expert weights carry a leading experts axis, under ``experts``
    and ``shared``."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, v = m["qk_nope_head_dim"], m["v_head_dim"]
    attn = {"wq": (d, H * (nope + rope)), "wkv_a": (d, r + rope),
            "wkv_b": (r, H * (nope + v)), "wo": (H * v, d)}
    if dense:
        f = m["intermediate_size"]
        return dict(attn, w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    f = m["moe_intermediate_size"]
    fs = f * m["n_shared_experts"]
    return dict(attn,
                experts={"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
                shared={"w_gate": (d, fs), "w_up": (d, fs),
                        "w_down": (fs, d)})


def _packed_leaf(key, K: int, N: int, lead: tuple, q: dict, w: dict) -> dict:
    ppb = CODES_PER_BYTE[q["bits"]]
    g = q["group_size"]
    kp, ks_, kz, ka = jax.random.split(key, 4)
    base = w["scale_times_sqrt_in"] / np.sqrt(K)
    lo, hi = w["act_scale"]
    return {
        "packed": jax.random.bits(kp, lead + (K // ppb, N), jnp.uint8),
        "scale": _bf16_round(_uniform(ks_, lead + (K // g, N),
                                      *w["scale_factor"]) * base),
        "zero": _bf16_round(_uniform(kz, lead + (K // g, N), *w["zero"])),
        # log-uniform on [lo, hi]; one per input channel of the layer
        "act_scale": _bf16_round(jnp.exp(_uniform(
            ka, (K,), float(np.log(lo)), float(np.log(hi))))),
    }


def layer_leaves(key, m: dict, q: dict, w: dict, dense: bool) -> dict:
    """One layer's leaves as plain arrays: norm gains, router and bias of an
    expert layer, and per linear ``packed``/``scale``/``zero``/
    ``act_scale``."""
    d, E = m["hidden_size"], m["n_routed_experts"]
    shapes = linear_shapes(m, dense)
    ks = jax.random.split(key, 16)
    gain = lambda k, n: _uniform(k, (n,), *w["norm_gain"]).astype(
        jnp.bfloat16)
    out = {"ln1": gain(ks[0], d), "ln2": gain(ks[1], d),
           "kv_norm": gain(ks[2], m["kv_lora_rank"])}
    names = ATTN + (FFN if dense else ())
    for name, k in zip(names, ks[3:]):
        out[name] = _packed_leaf(k, *shapes[name], (), q, w)
    if not dense:
        out["router"] = jax.random.normal(ks[10], (d, E), jnp.float32) \
            * (w["router_std"] / np.sqrt(d))
        out["bias"] = _uniform(ks[11], (E,), *w["router_bias"])
        out["experts"] = {n: _packed_leaf(k, *shapes["experts"][n], (E,),
                                          q, w)
                          for n, k in zip(FFN, jax.random.split(ks[12], 3))}
        out["shared"] = {n: _packed_leaf(k, *shapes["shared"][n], (), q, w)
                         for n, k in zip(FFN, jax.random.split(ks[13], 3))}
    return out


def top_leaves(key, m: dict, w: dict) -> dict:
    """Embedding, output head and final norm gain (bf16)."""
    d, V = m["hidden_size"], m["vocab_size"]
    ke, kh, kn = jax.random.split(key, 3)
    return {
        "embed": (jax.random.normal(ke, (V, d), jnp.float32)
                  * w["embed_std"]).astype(jnp.bfloat16),
        "head": (jax.random.normal(kh, (d, V), jnp.float32)
                 / np.sqrt(d)).astype(jnp.bfloat16),
        "ln_f": _uniform(kn, (d,), *w["norm_gain"]).astype(jnp.bfloat16),
    }


def _qtensor(leaf: dict, shape, q: dict):
    from repro.core.qtensor import QTensor
    return QTensor(packed=leaf["packed"], scale=leaf["scale"],
                   zero=leaf["zero"], bits=q["bits"],
                   group_size=q["group_size"], shape=tuple(shape),
                   act_scale=leaf["act_scale"])


def _to_blocks(layers: dict, m: dict, q: dict, dense: bool) -> dict:
    """Stacked layer arrays in ``pack_model``'s block tree of QTensors."""
    shapes = linear_shapes(m, dense)
    bp = {k: layers[k] for k in ("ln1", "ln2", "kv_norm")}
    for name in ATTN + (FFN if dense else ()):
        bp[name] = _qtensor(layers[name], shapes[name], q)
    if not dense:
        moe = {n: _qtensor(layers["experts"][n], shapes["experts"][n], q)
               for n in FFN}
        moe["router"], moe["bias"] = layers["router"], layers["bias"]
        moe["shared"] = {n: _qtensor(layers["shared"][n],
                                     shapes["shared"][n], q) for n in FFN}
        bp["moe"] = moe
    return bp


@functools.lru_cache(maxsize=None)
def _build_fn(m_items, q_items, w_items):
    """The jitted generator of every leaf, one per model (seeds share its
    compilation)."""
    m, q, w = (dict(x) for x in (m_items, q_items, w_items))
    n0, L = m["first_k_dense_replace"], m["num_hidden_layers"]

    def stack(kbase, lo, hi, dense):
        lkeys = jax.vmap(lambda i: layer_key(kbase, i))(jnp.arange(lo, hi))
        return jax.vmap(lambda k: layer_leaves(k, m, q, w, dense))(lkeys)

    return jax.jit(lambda ktop, kbase: (
        top_leaves(ktop, m, w), stack(kbase, 0, n0, True),
        stack(kbase, n0, L, False)))


def make_packed_params(seed: int, m: dict, q: dict, w: dict):
    """The whole packed tree, on the default device, in one jitted call."""
    top, dense, moe = _build_fn(_frozen(m), _frozen(q), _frozen(w))(
        *keys(seed))
    return {"embed": top["embed"],
            "dense_blocks": _to_blocks(dense, m, q, True),
            "blocks": _to_blocks(moe, m, q, False),
            "ln_f": top["ln_f"], "head": top["head"]}


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items, q_items, w_items, dense: bool):
    m, q, w = (dict(x) for x in (m_items, q_items, w_items))
    return jax.jit(lambda k: layer_leaves(k, m, q, w, dense))


def one_layer(seed: int, layer: int, m: dict, q: dict, w: dict) -> dict:
    """Layer ``layer``'s leaves alone (of all layers, dense ones first),
    bit-identical to its slice of :func:`make_packed_params`."""
    _, kbase = keys(seed)
    dense = layer < m["first_k_dense_replace"]
    fn = _layer_fn(_frozen(m), _frozen(q), _frozen(w), dense)
    return fn(layer_key(kbase, layer))


def top_only(seed: int, m: dict, w: dict) -> dict:
    ktop, _ = keys(seed)
    return jax.jit(lambda k: top_leaves(k, m, w))(ktop)
