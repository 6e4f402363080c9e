"""Block abstraction for block-wise reconstruction (paper Eq. 3).

Every architecture is decomposed into an ordered list of *stages*; each stage
is a run of structurally-identical blocks (decoder blocks, encoder blocks,
mamba blocks, the zamba2 shared-attention block...).  The calibration driver
(core/recon.py) walks stages block-by-block, collects inputs X and FP outputs
block(theta, X), optimizes the quantization parameters, and writes the
quantized block back — exactly the paper's Algorithm 1, generalized beyond
llama-style decoders.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, mla_moe, rwkv, ssm, transformer, vlm
from repro.models.common import Ctx, DEFAULT_CTX, take_layer

# Leaf names that are quantizable linear weights.  Everything else (norms,
# routers, conv kernels, decay LoRA, token-shift mixers, embeddings) stays
# FP16 — the paper's scheme targets matmul weights (DESIGN.md §4).
QUANT_LEAF_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "wr", "wg", "ck", "cv", "cr",                 # rwkv time/channel mix
    "in_proj", "out_proj",                        # mamba2
    "wkv_a", "wkv_b",                             # latent attention
})


def quant_leaf_paths(block_params) -> list:
    """Paths (as tuples of keys) of quantizable leaves inside one block."""
    out = []

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        else:
            if path and path[-1] in QUANT_LEAF_NAMES and node.ndim >= 2 \
                    and node.shape[-2] >= 2:
                out.append(path)
    walk(block_params, ())
    return out


def get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def set_path(tree, path, value):
    """Immutable set on nested dicts."""
    if not path:
        return value
    new = dict(tree)
    new[path[0]] = set_path(tree[path[0]], path[1:], value)
    return new


@dataclasses.dataclass
class Stage:
    name: str
    n_blocks: int
    get_block: Callable            # (params, i) -> block params
    set_block: Callable            # (params, i, bp) -> params
    init_x: Callable               # (params, batch, saved) -> (B, S, d) stream
    apply: Callable                # (bp, x, aux) -> x
    make_aux: Callable = lambda params, batch, saved: None
    save_as: Optional[str] = None  # store the stage's final stream under this key
    calibrate: bool = True
    # (param_key, layer_idx) a block maps to in the stacked param storage —
    # used by pack_model to assemble stacked QTensors
    pack_target: Callable = lambda i: ("blocks", i)


def _stacked_getset(key):
    def get(params, i):
        return take_layer(params[key], i)

    def set_(params, i, bp):
        new = dict(params)
        new[key] = jax.tree_util.tree_map(
            lambda full, one: full.at[i].set(one.astype(full.dtype))
            if not hasattr(full, "dequantize") else full,
            params[key], bp)
        return new
    return get, set_


def build_stages(cfg: ModelConfig, ctx: Ctx = DEFAULT_CTX) -> list:
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        prefix = cfg.num_patches if fam == "vlm" else None

        def init_x(params, batch, saved):
            if fam == "vlm":
                return vlm.assemble_inputs(params, cfg, batch["patches"],
                                           batch["tokens"])
            return transformer.embed_tokens(params, cfg, batch["tokens"])

        def apply(bp, x, aux):
            pos = jnp.arange(x.shape[1])
            out, _ = transformer.block(bp, x, cfg, ctx, positions=pos,
                                       prefix_len=prefix)
            return out

        get, set_ = _stacked_getset("blocks")
        return [Stage("decoder", cfg.num_layers, get, set_, init_x, apply)]

    if fam == "mla_moe":
        # two runs of blocks: the leading dense layers, then the expert
        # layers, one stream through both
        def init_x(params, batch, saved):
            return params["embed"][batch["tokens"]]

        def apply(bp, x, aux):
            out, _, _ = mla_moe.block(bp, x, cfg, ctx,
                                      positions=jnp.arange(x.shape[1]))
            return out

        stages = []
        for (key, _), n, first in zip(
                mla_moe.STACKS,
                (cfg.moe.dense_layers,
                 cfg.num_layers - cfg.moe.dense_layers), (True, False)):
            get, set_ = _stacked_getset(key)
            stages.append(Stage(
                key, n, get, set_,
                init_x if first else (lambda p, b, s: None), apply,
                pack_target=(lambda k: lambda i: (k, i))(key)))
        return stages

    if fam == "rwkv":
        def init_x(params, batch, saved):
            return params["embed"][batch["tokens"]]

        def apply(bp, x, aux):
            out, _ = rwkv.block(bp, x, cfg, ctx)
            return out

        get, set_ = _stacked_getset("blocks")
        return [Stage("rwkv", cfg.num_layers, get, set_, init_x, apply)]

    if fam == "hybrid":
        # forward order: mamba segments with the shared attn block interleaved.
        # The shared block is calibrated once (at its first site) and then
        # replayed; each slot i maps to either a mamba layer or a shared site.
        order = []
        for (s, e, attn_after) in hybrid._segments(cfg):
            order += [("mamba", i) for i in range(s, e)]
            if attn_after:
                order.append(("attn", len([o for o in order if o[0] == "attn"])))

        def get(params, i):
            kind, j = order[i]
            if kind == "mamba":
                return take_layer(params["blocks"], j)
            return take_layer(params["shared_attn"], 0)

        def set_(params, i, bp):
            kind, j = order[i]
            new = dict(params)
            if kind == "mamba":
                new["blocks"] = jax.tree_util.tree_map(
                    lambda full, one: full.at[j].set(one.astype(full.dtype))
                    if not hasattr(full, "dequantize") else full,
                    params["blocks"], bp)
            else:
                new["shared_attn"] = jax.tree_util.tree_map(
                    lambda full, one: one[None] if not hasattr(full, "dequantize")
                    else full, params["shared_attn"], bp)
            return new

        def init_x(params, batch, saved):
            return params["embed"][batch["tokens"]]

        def apply_i(i):
            kind, _ = order[i]
            if kind == "mamba":
                def f(bp, x, aux):
                    out, _, _ = ssm.mamba_block(bp, x, cfg, ctx)
                    return out
            else:
                def f(bp, x, aux):
                    out, _ = transformer.block(
                        bp, x, cfg.replace(family="dense"), ctx,
                        positions=jnp.arange(x.shape[1]))
                    return out
            return f

        seen_attn = False
        stages = []
        for i, (kind, j) in enumerate(order):
            calibrate = True
            if kind == "attn":
                calibrate = not seen_attn     # shared weights: calibrate once
                seen_attn = True
            tgt = ("blocks", j) if kind == "mamba" else ("shared_attn", 0)
            stages.append(Stage(f"{kind}{j}", 1,
                                (lambda i: lambda p, _: get(p, i))(i),
                                (lambda i: lambda p, _, bp: set_(p, i, bp))(i),
                                init_x if i == 0 else (lambda p, b, s: None),
                                apply_i(i), calibrate=calibrate,
                                pack_target=(lambda t: lambda _i: t)(tgt)))
        return stages

    if fam == "encdec":
        def enc_init(params, batch, saved):
            from repro.models import layers as L
            f = batch["frames"]
            return f + L.sinusoidal_pos(f.shape[1], cfg.d_model, f.dtype)[None]

        def enc_apply(bp, x, aux):
            return encdec.encoder_block(bp, x, cfg, ctx)

        def dec_init(params, batch, saved):
            from repro.models import layers as L
            t = batch["tokens"]
            x = params["embed"][t]
            return x + L.sinusoidal_pos(x.shape[1], cfg.d_model, x.dtype)[None]

        def dec_aux(params, batch, saved):
            from repro.models import layers as L
            enc = saved["enc"]
            return L.layer_norm(enc, params["ln_enc"],
                                jnp.zeros_like(params["ln_enc"]), cfg.norm_eps)

        def dec_apply(bp, x, aux):
            out, _ = encdec.decoder_block(bp, x, aux, cfg, ctx)
            return out

        eget, eset = _stacked_getset("encoder")
        dget, dset = _stacked_getset("decoder")
        return [
            Stage("encoder", cfg.encoder_layers, eget, eset, enc_init,
                  enc_apply, save_as="enc",
                  pack_target=lambda i: ("encoder", i)),
            Stage("decoder", cfg.num_layers, dget, dset, dec_init, dec_apply,
                  make_aux=dec_aux, pack_target=lambda i: ("decoder", i)),
        ]

    raise ValueError(fam)
