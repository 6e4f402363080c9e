"""Uniform model API over all families.

``get_model(cfg)`` returns a ``Model`` with:
    init_params(key) -> params
    loss_fn(params, batch, ctx) -> scalar           (train step core)
    prefill(params, batch, cache, ctx) -> (logits, cache)
    decode_step(params, cache, tokens, pos, ctx, active, ptab) -> (logits, cache)
    init_cache(batch, max_seq, dtype) -> cache
    cache_spec: CacheSpec                           (declared cache layout)
    prefill_record / decode_record: the same steps returning also
        a dict of device arrays the scheduler fetches after the wave
        (None for families that record nothing)
    serve_params(params) -> params                  (once, before serving)
Batches are dicts: {"tokens"} (+ "frames" for encdec, "patches" for vlm).

``cache_spec`` is the explicit cache contract (see README "Cache
contract"): which leaves the family's cache has, which of them carry a
per-token extent (and can therefore live in a page pool), whether the
family's prefill can resume mid-sequence (chunked prefill), and whether
prompt-prefix pages may be shared copy-on-write.  ``ptab`` is the
per-slot page table a paged ``CacheStore`` threads through decode; dense
runs pass None and families without token leaves ignore it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, mla_moe, rwkv, transformer, vlm
from repro.models.common import (CacheSpec, DEFAULT_CTX, LEAF_FIXED,
                                 LEAF_STATE, LEAF_TOKEN, LeafSpec)

_TOKEN = LeafSpec(LEAF_TOKEN, token_axis=2)
_STATE = LeafSpec(LEAF_STATE)
_FIXED = LeafSpec(LEAF_FIXED)

# Family cache contracts.  Chunkable/shareable rationale:
#   dense  — every per-position op is row-independent, so prefill can stop
#            and resume at any boundary and full prompt-prefix pages hold
#            KV determined solely by the shared tokens -> both True.
#   moe    — expert capacity dispatch couples sequence positions (tokens
#            compete for per-expert capacity within one prefill call), so
#            splitting prefill changes outputs -> not chunkable.
#   rwkv/hybrid — recurrent state (wkv / mamba conv+ssm) summarizes the
#            whole past; the in-tree prefill can't restart mid-sequence.
#   encdec — decoder positions are resumable in principle, but prefill
#            also builds the cross-attention cache from the encoder pass;
#            kept whole-prefill here.
#   vlm    — the image-patch prefix (prefix-LM mask) complicates chunk
#            boundaries; kept whole-prefill, never shared (patch
#            embeddings aren't captured by prompt-token identity).
#   mla_moe — one latent token leaf per layer kind; prefill attends over
#            the prompt's own latents only, and the decode kernel reads
#            dense slot lanes: whole prefill on the dense store only.
CACHE_SPECS = {
    "dense": CacheSpec("dense", (("k", _TOKEN), ("v", _TOKEN)),
                       chunkable=True, shareable=True),
    "moe": CacheSpec("moe", (("k", _TOKEN), ("v", _TOKEN))),
    "rwkv": CacheSpec("rwkv", (("shift1", _STATE), ("shift2", _STATE),
                               ("wkv", _STATE))),
    "hybrid": CacheSpec("hybrid", (("attn_k", _TOKEN), ("attn_v", _TOKEN),
                                   ("mamba/conv", _STATE),
                                   ("mamba/ssm", _STATE))),
    "encdec": CacheSpec("encdec", (("self_k", _TOKEN), ("self_v", _TOKEN),
                                   ("cross_k", _FIXED), ("cross_v", _FIXED))),
    "vlm": CacheSpec("vlm", (("k", _TOKEN), ("v", _TOKEN))),
    "mla_moe": CacheSpec("mla_moe", (("latent", _TOKEN),
                                     ("latent_dense", _TOKEN)),
                         pageable=False),
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init_params: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable
    cache_spec: CacheSpec
    # prefill / decode that also return (..., record): a dict of device
    # arrays the scheduler fetches after the wave (the mla_moe family's
    # routing counters and per-token experts); None for families without
    prefill_record: Optional[Callable] = None
    decode_record: Optional[Callable] = None
    # the params as serving wants them, made once before serving
    serve_params: Callable = lambda params: params


def get_model(cfg: ModelConfig) -> Model:
    fam = cfg.family
    spec = CACHE_SPECS[fam] if fam in CACHE_SPECS else None
    if fam in ("dense", "moe"):
        return Model(
            cfg,
            init_params=lambda key: transformer.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: transformer.loss_fn(p, cfg, b, ctx),
            prefill=lambda p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None:
                transformer.prefill(p, cfg, b["tokens"], c, ctx,
                                    start_pos=start_pos, ptab=ptab),
            decode_step=lambda p, c, t, pos, ctx=DEFAULT_CTX, active=None, ptab=None:
                transformer.decode_step(p, cfg, c, t, pos, ctx, active=active,
                                        ptab=ptab),
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                transformer.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
        )
    if fam == "mla_moe":
        def prefill_record(p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None):
            return mla_moe.prefill(p, cfg, b["tokens"], c, ctx,
                                   start_pos=start_pos, ptab=ptab)

        def decode_record(p, c, t, pos, ctx=DEFAULT_CTX, active=None,
                          ptab=None):
            return mla_moe.decode_step(p, cfg, c, t, pos, ctx, active=active,
                                       ptab=ptab)
        return Model(
            cfg,
            init_params=lambda key: mla_moe.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: mla_moe.loss_fn(p, cfg, b, ctx),
            prefill=lambda *a, **kw: prefill_record(*a, **kw)[:2],
            decode_step=lambda *a, **kw: decode_record(*a, **kw)[:2],
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                mla_moe.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
            prefill_record=prefill_record,
            decode_record=decode_record,
            serve_params=lambda params: mla_moe.serve_params(params, cfg),
        )
    if fam == "rwkv":
        return Model(
            cfg,
            init_params=lambda key: rwkv.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: rwkv.loss_fn(p, cfg, b, ctx),
            prefill=lambda p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None:
                rwkv.prefill(p, cfg, b["tokens"], c, ctx),
            decode_step=lambda p, c, t, pos, ctx=DEFAULT_CTX, active=None, ptab=None:
                rwkv.decode_step(p, cfg, c, t, pos, ctx, active=active),
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                rwkv.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
        )
    if fam == "hybrid":
        return Model(
            cfg,
            init_params=lambda key: hybrid.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: hybrid.loss_fn(p, cfg, b, ctx),
            prefill=lambda p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None:
                hybrid.prefill(p, cfg, b["tokens"], c, ctx, ptab=ptab),
            decode_step=lambda p, c, t, pos, ctx=DEFAULT_CTX, active=None, ptab=None:
                hybrid.decode_step(p, cfg, c, t, pos, ctx, active=active,
                                   ptab=ptab),
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                hybrid.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
        )
    if fam == "encdec":
        return Model(
            cfg,
            init_params=lambda key: encdec.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: encdec.loss_fn(p, cfg, b, ctx),
            prefill=lambda p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None:
                encdec.prefill(p, cfg, b["frames"], b["tokens"], c, ctx,
                               ptab=ptab),
            decode_step=lambda p, c, t, pos, ctx=DEFAULT_CTX, active=None, ptab=None:
                encdec.decode_step(p, cfg, c, t, pos, ctx, active=active,
                                   ptab=ptab),
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                encdec.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
        )
    if fam == "vlm":
        return Model(
            cfg,
            init_params=lambda key: vlm.init_params(cfg, key),
            loss_fn=lambda p, b, ctx=DEFAULT_CTX: vlm.loss_fn(p, cfg, b, ctx),
            prefill=lambda p, b, c, ctx=DEFAULT_CTX, start_pos=0, ptab=None:
                vlm.prefill(p, cfg, b["patches"], b["tokens"], c, ctx,
                            ptab=ptab),
            decode_step=lambda p, c, t, pos, ctx=DEFAULT_CTX, active=None, ptab=None:
                vlm.decode_step(p, cfg, c, t, pos, ctx, active=active,
                                ptab=ptab),
            init_cache=lambda batch, max_seq, dtype=jnp.bfloat16:
                vlm.init_cache(cfg, batch, max_seq, dtype),
            cache_spec=spec,
        )
    raise ValueError(f"unknown family {fam!r}")
