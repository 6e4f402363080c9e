"""Dense decoder-only transformer (llama family: smollm, tinyllama, llama2-7b,
command-r-35b, llama3-405b; also the gemma backbone of paligemma).

Layers are stacked along a leading L axis and iterated with ``lax.scan`` so the
HLO stays O(1) in depth (essential for the 126-layer 405B dry-run).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.common import (Ctx, DEFAULT_CTX, cache_layer_loop,
                                 gather_pages, layer_loop, maybe_remat,
                                 page_update_cache, update_cache, write_rows,
                                 zeros_jit)
from repro.models.moe import init_moe_ffn, moe_ffn


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_block_params(cfg: ModelConfig, key, n_layers: int) -> dict:
    """Stacked (L, ...) decoder-block params."""
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)

    def stack(k, shape, scale=None):
        sc = scale if scale is not None else shape[-2] ** -0.5
        return (jax.random.normal(k, (n_layers,) + shape, jnp.float32) * sc).astype(dt)

    p = {
        "ln1": jnp.ones((n_layers, d), dt),
        "wq": stack(ks[0], (d, cfg.num_heads * hd)),
        "wk": stack(ks[1], (d, cfg.num_kv_heads * hd)),
        "wv": stack(ks[2], (d, cfg.num_kv_heads * hd)),
        "wo": stack(ks[3], (cfg.num_heads * hd, d)),
        "ln2": jnp.ones((n_layers, d), dt),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe_ffn(cfg, ks[4], n_layers)
    else:
        p["w_gate"] = stack(ks[4], (d, f))
        p["w_up"] = stack(ks[5], (d, f))
        p["w_down"] = stack(ks[6], (f, d))
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model), jnp.float32)
                  * cfg.d_model ** -0.5).astype(dt),
        "blocks": init_block_params(cfg, k2, cfg.num_layers),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(k3, cfg.d_model, cfg.vocab_size, dt)
    return params


# --------------------------------------------------------------------------
# one decoder block (also the unit TesseraQ reconstructs)
# --------------------------------------------------------------------------

def attention(bp: dict, x: jax.Array, cfg: ModelConfig, ctx: Ctx, *,
              positions, kv_cache=None, cache_pos=None, kv_len=None,
              prefix_len: Optional[int] = None, active=None, ptab=None,
              layer=None):
    """Self-attention with optional KV cache.  Returns (out, new_kv or None).

    ``layer`` (int32 scalar, one-token decode over a dense store only):
    ``kv_cache`` holds the stacked leaves (L, B, S_max, H, D), this layer's
    rows are written into them in place and attention reads them by index;
    the stacked leaves come back as ``new_kv``.

    ``ptab`` (B, W) int32 + ``ctx.page_size > 0`` switches the cache to
    paged mode: the k/v leaves are page POOLS (num_pages, page_size, H, D)
    shared across slots, writes scatter through the page table, and reads
    either walk the table in the pallas decode kernel or gather a virtual
    slot-major cache whose shape equals the dense lane — which is what
    keeps paged outputs bit-identical to dense under exact masking."""
    Bb, S, d = x.shape
    hd = cfg.resolved_head_dim
    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    if ctx.act_bits:
        h = L.fake_quant_act(h, ctx.act_bits)
    kb = ctx.kernel_backend
    q = L.matmul(h, bp["wq"], kb).reshape(Bb, S, cfg.num_heads, hd)
    k = L.matmul(h, bp["wk"], kb).reshape(Bb, S, cfg.num_kv_heads, hd)
    v = L.matmul(h, bp["wv"], kb).reshape(Bb, S, cfg.num_kv_heads, hd)
    if cfg.rope_theta:
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    q = ctx.shard(q, ("batch", "seq", "heads", None))
    k = ctx.shard(k, ("batch", "seq", "kv_heads", None))
    v = ctx.shard(v, ("batch", "seq", "kv_heads", None))

    new_kv = None
    pages_arg = None
    if kv_cache is not None:
        ks, vs = k, v
        if ctx.kv_bits:
            qmax = (1 << (ctx.kv_bits - 1)) - 1
            quant = lambda a: jnp.clip(
                jnp.round(a.astype(jnp.float32) / ctx.kv_scale),
                -qmax - 1, qmax).astype(kv_cache["k"].dtype)
            ks, vs = quant(k), quant(v)
        paged = ctx.page_size > 0 and ptab is not None
        if paged:
            ck, cv = page_update_cache(kv_cache["k"], kv_cache["v"], ks, vs,
                                       cache_pos, ptab, ctx.page_size)
        elif layer is not None:
            ck = write_rows(kv_cache["k"], layer, ks[:, 0], cache_pos, kb)
            cv = write_rows(kv_cache["v"], layer, vs[:, 0], cache_pos, kb)
        else:
            ck, cv = update_cache(kv_cache["k"], kv_cache["v"], ks, vs,
                                  cache_pos)
        new_kv = {"k": ck, "v": cv}
        if ctx.kv_bits:
            # int8 pools dequantize AFTER gathering (the pallas paged walk
            # is fp-only, so paged int8 KV takes the gather + dense path);
            # an int8 stacked leaf dequantizes its layer's slice
            if paged:
                ck, cv = gather_pages(ck, ptab), gather_pages(cv, ptab)
            if layer is not None:
                ck, cv, layer = ck[layer], cv[layer], None
            attn_k = ck.astype(x.dtype) * jnp.asarray(ctx.kv_scale, x.dtype)
            attn_v = cv.astype(x.dtype) * jnp.asarray(ctx.kv_scale, x.dtype)
        else:
            attn_k, attn_v = ck, cv
            if paged:
                pages_arg = (ptab, ctx.page_size)
        q_offset = cache_pos
        valid = kv_len if kv_len is not None else cache_pos + S
    else:
        attn_k, attn_v = k, v
        q_offset = 0
        valid = None

    o = L.flash_attention(q, attn_k, attn_v, causal=True, q_offset=q_offset,
                          kv_len=valid, chunk=ctx.attn_chunk,
                          prefix_len=prefix_len, backend=kb, active=active,
                          pages=pages_arg, layer=layer)
    o = o.reshape(Bb, S, cfg.num_heads * hd)
    if ctx.act_bits:
        o = L.fake_quant_act(o, ctx.act_bits)
    return L.matmul(o, bp["wo"], kb), new_kv


def ffn(bp: dict, x: jax.Array, cfg: ModelConfig, ctx: Ctx) -> jax.Array:
    h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if ctx.act_bits:
        h = L.fake_quant_act(h, ctx.act_bits)
    if cfg.family == "moe":
        return moe_ffn(bp["moe"], h, cfg, ctx)
    kb = ctx.kernel_backend
    g = L.matmul(h, bp["w_gate"], kb)
    u = L.matmul(h, bp["w_up"], kb)
    a = jax.nn.silu(g) * u
    if ctx.act_bits:
        a = L.fake_quant_act(a, ctx.act_bits)
    return L.matmul(a, bp["w_down"], kb)


def block(bp: dict, x: jax.Array, cfg: ModelConfig, ctx: Ctx = DEFAULT_CTX, *,
          positions, kv_cache=None, cache_pos=None, kv_len=None,
          prefix_len=None, active=None, ptab=None, layer=None):
    a, new_kv = attention(bp, x, cfg, ctx, positions=positions,
                          kv_cache=kv_cache, cache_pos=cache_pos,
                          kv_len=kv_len, prefix_len=prefix_len, active=active,
                          ptab=ptab, layer=layer)
    x = x + a
    x = x + ffn(bp, x, cfg, ctx)
    x = ctx.shard(x, ("batch", "res_seq", "embed"))
    return x, new_kv


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens) -> jax.Array:
    e = params["embed"][tokens]
    if cfg.family == "vlm":                      # gemma input scaling
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return e


def unembed(params, cfg: ModelConfig, x, ctx: Ctx = DEFAULT_CTX) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return L.matmul(x, params["head"], ctx.kernel_backend)


def forward(params, cfg: ModelConfig, tokens, ctx: Ctx = DEFAULT_CTX, *,
            inputs_embeds=None, prefix_len=None) -> jax.Array:
    """Training/prefill forward without cache.  Returns logits (B, S, V)."""
    x = inputs_embeds if inputs_embeds is not None else embed_tokens(params, cfg, tokens)
    B, S = x.shape[:2]
    x = ctx.shard(x, ("batch", "res_seq", "embed"))
    positions = jnp.arange(S)

    def step(h, bp):
        h, _ = block(bp, h, cfg, ctx, positions=positions, prefix_len=prefix_len)
        return h, ()

    x, _ = layer_loop(maybe_remat(step, ctx), x, params["blocks"],
                      cfg.unroll_layers)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params, cfg, x, ctx)
    return ctx.shard(logits, ("batch", "seq", "vocab"))


def loss_fn(params, cfg: ModelConfig, batch, ctx: Ctx = DEFAULT_CTX):
    """Next-token cross entropy. batch = {tokens, (optional) loss_mask}."""
    tokens = batch["tokens"]
    logits = forward(params, cfg, tokens[:, :-1], ctx,
                     inputs_embeds=batch.get("inputs_embeds"))
    targets = tokens[:, 1:]
    lw = batch.get("loss_mask")
    lw = lw[:, 1:] if lw is not None else jnp.ones_like(targets, jnp.float32)
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * lw
    return nll.sum() / jnp.maximum(lw.sum(), 1.0)


# -- serving ----------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, hd)
    return {"k": zeros_jit(shape, dtype), "v": zeros_jit(shape, dtype)}


def prefill(params, cfg: ModelConfig, tokens, cache, ctx: Ctx = DEFAULT_CTX, *,
            inputs_embeds=None, prefix_len=None, start_pos=0, ptab=None):
    """Fill cache from position ``start_pos``; returns (last_logits, cache).

    ``start_pos > 0`` resumes a chunked prefill: this call's tokens are
    positions [start_pos, start_pos + S) and attend causally over the
    cache contents written by earlier chunks (plus themselves).  Every
    per-position op is row-independent and masked lanes are exact -1e30
    no-ops, so chunking changes reduction grouping only — and not even
    that when dense and paged runs use the SAME chunk schedule."""
    x = inputs_embeds if inputs_embeds is not None else embed_tokens(params, cfg, tokens)
    B, S = x.shape[:2]
    x = ctx.shard(x, ("batch", "res_seq", "embed"))
    positions = jnp.asarray(start_pos, jnp.int32) + jnp.arange(S)
    pos0 = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (B,))

    def step(h, layer):
        bp, kv = layer
        h, new_kv = block(bp, h, cfg, ctx, positions=positions, kv_cache=kv,
                          cache_pos=pos0, prefix_len=prefix_len, ptab=ptab)
        return h, new_kv

    x, new_cache = layer_loop(step, x, (params["blocks"], cache),
                              cfg.unroll_layers)
    x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    logits = unembed(params, cfg, x, ctx)[:, 0]
    return ctx.shard(logits, ("batch", "vocab")), new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                ctx: Ctx = DEFAULT_CTX, *, active=None, ptab=None):
    """One decode step. tokens: (B,), pos: (B,) current write position.
    ``active``: (B,) slot-occupancy vector from the scheduler — the
    slot-aware decode attention kernel skips dead slots entirely.
    ``ptab``: (B, W) page table when the cache is a page pool.

    Over a dense store the stacked cache rides in the layer loop's carry
    (:func:`~repro.models.common.cache_layer_loop`): each layer writes one
    row a slot in place and attention reads the layer by index.  The page
    pools keep the scan over ``xs``; their writes are already a scatter."""
    x = embed_tokens(params, cfg, tokens)[:, None, :]
    x = ctx.shard(x, ("batch", "res_seq", "embed"))
    kw = dict(positions=pos[:, None], cache_pos=pos, kv_len=pos + 1,
              active=active, ptab=ptab)

    if ptab is None:
        def carried(h, kv, i, bp):
            h, kv = block(bp, h, cfg, ctx, kv_cache=kv, layer=i, **kw)
            return h, kv, ()

        x, new_cache, _ = cache_layer_loop(carried, x, cache,
                                           params["blocks"],
                                           cfg.unroll_layers)
    else:
        def step(h, layer):
            bp, kv = layer
            h, new_kv = block(bp, h, cfg, ctx, kv_cache=kv, **kw)
            return h, new_kv

        x, new_cache = layer_loop(step, x, (params["blocks"], cache),
                                  cfg.unroll_layers)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params, cfg, x, ctx)[:, 0]
    return ctx.shard(logits, ("batch", "vocab")), new_cache
