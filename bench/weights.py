"""The packed serving artifact of a dense decoder, made on the device from a seed.

The tree has exactly the structure ``repro.core.pack_model`` returns for a
llama-style decoder quantized with AWQ: bf16 ``embed``/``head``/norm gains,
and one stacked ``QTensor`` per block linear (uint8 codes packed along the
input axis, float32 scale and zero per group, float32 AWQ ``act_scale`` per
input channel).  Codes are uniform random; scale, zero, act_scale and the
norm gains are random in the ranges the configuration file states, and are
rounded to bfloat16 values so that the program's bf16 dequantization and the
float32 reference see the same numbers.

Every leaf of layer ``l`` comes from its own key, ``fold_in(layer_key, l)``,
so the reference regenerates one layer at a time (:func:`one_layer`) and
gets the same bits as the stacked tree made in one jitted call
(:func:`make_packed_params`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
CODES_PER_BYTE = {2: 4, 4: 2, 8: 1}


def root_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32 bits
    too): ``SeedSequence`` folds the whole integer into two 32-bit words."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def linear_shapes(m: dict) -> dict:
    """(in_features, out_features) of each block linear."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _bf16_round(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def layer_leaves(key, m: dict, q: dict, w: dict) -> dict:
    """One layer's leaves as plain arrays: ``ln1``/``ln2`` gains and, per
    linear, ``packed``/``scale``/``zero``/``act_scale``."""
    ppb = CODES_PER_BYTE[q["bits"]]
    g = q["group_size"]
    ks = jax.random.split(key, 2 + len(LINEARS))
    d = m["hidden_size"]
    out = {"ln1": _uniform(ks[0], (d,), *w["norm_gain"]).astype(jnp.bfloat16),
           "ln2": _uniform(ks[1], (d,), *w["norm_gain"]).astype(jnp.bfloat16)}
    for name, k in zip(LINEARS, ks[2:]):
        K, N = linear_shapes(m)[name]
        kp, ks_, kz, ka = jax.random.split(k, 4)
        base = w["scale_times_sqrt_in"] / np.sqrt(K)
        lo, hi = w["act_scale"]
        out[name] = {
            "packed": jax.random.bits(kp, (K // ppb, N), jnp.uint8),
            "scale": _bf16_round(_uniform(ks_, (K // g, N), *w["scale_factor"])
                                 * base),
            "zero": _bf16_round(_uniform(kz, (K // g, N), *w["zero"])),
            # log-uniform on [lo, hi]
            "act_scale": _bf16_round(jnp.exp(_uniform(
                ka, (K,), float(np.log(lo)), float(np.log(hi))))),
        }
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


def keys(seed: int):
    """(top-level key, per-layer base key) for a seed."""
    k = root_key(seed)
    return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def layer_key(base, layer: int):
    return jax.random.fold_in(base, layer)


def _to_tree(top: dict, layers: dict, q: dict, m: dict):
    """Arrange generated arrays into ``pack_model``'s tree of QTensors."""
    from repro.core.qtensor import QTensor
    blocks = {"ln1": layers["ln1"], "ln2": layers["ln2"]}
    for name in LINEARS:
        leaf = layers[name]
        blocks[name] = QTensor(
            packed=leaf["packed"], scale=leaf["scale"], zero=leaf["zero"],
            bits=q["bits"], group_size=q["group_size"],
            shape=linear_shapes(m)[name], act_scale=leaf["act_scale"])
    return {"embed": top["embed"], "blocks": blocks, "ln_f": top["ln_f"],
            "head": top["head"]}


def make_packed_params(seed: int, m: dict, q: dict, w: dict):
    """The whole packed tree, on the default device, in one jitted call."""
    ktop, kbase = keys(seed)
    n = m["num_hidden_layers"]

    @jax.jit
    def build(ktop, kbase):
        lkeys = jax.vmap(lambda i: layer_key(kbase, i))(jnp.arange(n))
        layers = jax.vmap(lambda k: layer_leaves(k, m, q, w))(lkeys)
        return top_leaves(ktop, m, w), layers

    top, layers = build(ktop, kbase)
    return _to_tree(top, layers, q, m)


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items, q_items, w_items):
    m, q, w = (dict(x) for x in (m_items, q_items, w_items))
    return jax.jit(lambda k: layer_leaves(k, m, q, w))


def _frozen(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def one_layer(seed: int, layer: int, m: dict, q: dict, w: dict) -> dict:
    """Layer ``layer``'s leaves alone, bit-identical to its slice of
    :func:`make_packed_params`."""
    _, kbase = keys(seed)
    fn = _layer_fn(_frozen(m), _frozen(q), _frozen(w))
    return fn(layer_key(kbase, layer))


def top_only(seed: int, m: dict, w: dict) -> dict:
    ktop, _ = keys(seed)
    return jax.jit(lambda k: top_leaves(k, m, w))(ktop)
