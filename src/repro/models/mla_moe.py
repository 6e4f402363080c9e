"""DeepSeek-V3-style decoder (Moonlight-16B-A3B): multi-head latent
attention and dropless expert layers after a leading dense layer.

Equations (DeepSeek-V2, arXiv:2405.04434; DeepSeek-V3, arXiv:2412.19437),
without a query low-rank projection:

  * attention: ``q = h W_q`` (per head ``qk_nope + qk_rope`` lanes);
    ``[c, k_pe] = h W_kv_a`` with ``c`` RMS-normalised by its own gain;
    ``[k_nope, v] = c W_kv_b`` per head; rotary embedding on the rope lanes
    only, one ``k_pe`` shared by every head, lanes paired ``(2i, 2i+1)`` as
    the model's own code pairs them; softmax scale ``1/sqrt(qk_nope +
    qk_rope)``.
  * expert layer: router ``s = sigmoid(h W_r)`` in float32; the top
    ``top_k`` experts of ``s + bias``; weights the chosen ``s`` normalised
    to sum 1 and times ``routed_scaling``; plus the shared experts (one
    SwiGLU of width ``shared_experts * d_ff``).  Layers ``< dense_layers``
    are a plain SwiGLU of width ``dense_d_ff``.

The cache holds one token leaf per layer kind, ``(layers, slots, S,
kv_lora_rank + qk_rope)``: ``[RMSNorm(c), rope(k_pe)]`` of every position.
Prefill attends in the decompressed form over the prompt's own latents.
Decode attends in the absorbed form, straight over the cache: ``q_lat[h] =
W_UK[h] q_nope[h]``, scores ``[q_lat, q_pe] . [c, k_pe]``, output
``W_UV[h] sum_s p_s c_s``; that is one 576-wide KV head shared by the 16
query heads, with V the first ``kv_lora_rank`` lanes of K.  The absorbed
``W_UK``/``W_UV`` come from ``wkv_b`` (its AWQ scale folded in); a serving
caller makes them once with :func:`serve_params`, and a decode step without
them makes them itself.

Routing is dropless: (token, choice) pairs are sorted by expert, each
expert's rows padded to the grouped kernel's row tile, the three expert
matmuls run over that layout (``layers.grouped_matmul``) and the rows are
gathered back.  Every row's result depends on that row alone, so a
scheduled batch gives each request what it gets served alone.  Rows of
slots the scheduler marks inactive are routed to no expert.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qtensor import QTensor
from repro.models import layers as L
from repro.models.common import Ctx, DEFAULT_CTX, cache_layer_loop, \
    layer_loop, maybe_remat, write_rows, zeros_jit

# the stacked parameter groups, in forward order, and their cache leaves
STACKS = (("dense_blocks", "latent_dense"), ("blocks", "latent"))
# What a serving step records per expert layer, on the device (the
# scheduler fetches it after the wave; see ``launch.steps``): per decode
# step ("step") the experts with a row and the largest expert's rows
# (ServeResult.step_counters); per token ("token"), prefill and decode
# alike, the experts it was routed to (the per-request record "experts",
# in the order of the request's positions).
STEP_COUNTERS = ("experts_touched", "largest_group")
EXPERTS = "experts"


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _attn_params(cfg: ModelConfig, key, n: int, stack) -> dict:
    d, H, m = cfg.d_model, cfg.num_heads, cfg.mla
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "ln1": jnp.ones((n, d), dt),
        "wq": stack(ks[0], (d, H * m.qk_head_dim)),
        "wkv_a": stack(ks[1], (d, m.latent_dim)),
        "kv_norm": jnp.ones((n, m.kv_lora_rank), dt),
        "wkv_b": stack(ks[2], (m.kv_lora_rank,
                               H * (m.qk_nope_head_dim + m.v_head_dim))),
        "wo": stack(ks[3], (H * m.v_head_dim, d)),
        "ln2": jnp.ones((n, d), dt),
    }


def _swiglu_params(key, d: int, f: int, lead: tuple, stack) -> dict:
    ks = jax.random.split(key, 3)
    return {"w_gate": stack(ks[0], lead + (d, f)),
            "w_up": stack(ks[1], lead + (d, f)),
            "w_down": stack(ks[2], lead + (f, d))}


def init_params(cfg: ModelConfig, key) -> dict:
    d, e = cfg.d_model, cfg.moe
    dt = jnp.dtype(cfg.dtype)
    n0, n1 = e.dense_layers, cfg.num_layers - e.dense_layers
    ks = jax.random.split(key, 8)

    def stacker(n):
        def stack(k, shape):
            return (jax.random.normal(k, (n,) + shape, jnp.float32)
                    * shape[-2] ** -0.5).astype(dt)
        return stack

    dense = _attn_params(cfg, ks[0], n0, stacker(n0))
    dense.update(_swiglu_params(ks[1], d, e.dense_d_ff, (), stacker(n0)))
    blocks = _attn_params(cfg, ks[2], n1, stacker(n1))
    moe = _swiglu_params(ks[3], d, cfg.d_ff, (e.num_experts,), stacker(n1))
    moe["router"] = (jax.random.normal(ks[4], (n1, d, e.num_experts),
                                       jnp.float32) * d ** -0.5)
    # a trained model's correction bias is small against the scores
    moe["bias"] = jax.random.uniform(ks[5], (n1, e.num_experts),
                                     jnp.float32, -0.05, 0.05)
    moe["shared"] = _swiglu_params(ks[6], d, cfg.d_ff * e.shared_experts,
                                   (), stacker(n1))
    blocks["moe"] = moe
    k_e, k_h = jax.random.split(ks[7])
    return {
        "embed": (jax.random.normal(k_e, (cfg.vocab_size, d), jnp.float32)
                  * d ** -0.5).astype(dt),
        "dense_blocks": dense,
        "blocks": blocks,
        "ln_f": jnp.ones((d,), dt),
        "head": L.dense_init(k_h, d, cfg.vocab_size, dt),
    }


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    n0 = cfg.moe.dense_layers
    tail = (batch, max_seq, cfg.mla.latent_dim)
    return {"latent_dense": zeros_jit((n0,) + tail, dtype),
            "latent": zeros_jit((cfg.num_layers - n0,) + tail, dtype)}


# --------------------------------------------------------------------------
# latent attention
# --------------------------------------------------------------------------

def rope_pairs(x: jax.Array, positions, theta: float) -> jax.Array:
    """Rotary embedding with lanes paired ``(2i, 2i + 1)``, the output in
    the order ``[rotated evens, rotated odds]``, as the model's code
    de-interleaves before ``rotate_half``.  x: (B, S, H, D); positions
    (B, S) or (S,).  Queries and keys go through the same permutation, so
    their dot products are those of the paired rotation."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    ev, od = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin],
                           axis=-1).astype(x.dtype)


def absorbed_weights(wkv_b, cfg: ModelConfig, dtype):
    """(W_UK (H, qk_nope, r), W_UV (H, r, v)) of one layer's ``wkv_b`` (r,
    H * (qk_nope + v)), with its AWQ input scale folded in: ``c W_kv_b =
    (c / act_scale) dequant(W)``."""
    m, H = cfg.mla, cfg.num_heads
    if isinstance(wkv_b, QTensor):
        w = wkv_b.dequantize(jnp.float32)
        if wkv_b.act_scale is not None:
            w = w / wkv_b.act_scale.astype(jnp.float32)[:, None]
    else:
        w = wkv_b.astype(jnp.float32)
    w = w.reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = w[..., :m.qk_nope_head_dim].transpose(1, 2, 0)
    w_uv = w[..., m.qk_nope_head_dim:].transpose(1, 0, 2)
    return w_uk.astype(dtype), w_uv.astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _absorbed_stack(wkv_b, cfg: ModelConfig):
    return jax.vmap(lambda w: absorbed_weights(w, cfg, jnp.dtype(cfg.dtype))
                    )(wkv_b)


def serve_params(params, cfg: ModelConfig):
    """``params`` with each layer's absorbed ``w_uk``/``w_uv`` beside its
    ``wkv_b``: made once, so that decode steps do not rebuild them."""
    out = dict(params)
    for key, _ in STACKS:
        bp = params[key]
        if "w_uk" not in bp:
            w_uk, w_uv = _absorbed_stack(bp["wkv_b"], cfg)
            out[key] = dict(bp, w_uk=w_uk, w_uv=w_uv)
    return out


def _project(bp, h, cfg: ModelConfig, ctx: Ctx, positions):
    """(q_nope (B,S,H,n), q_pe (B,S,H,r), latent (B,S,kv_lora+r))."""
    B, S, _ = h.shape
    m, H, kb = cfg.mla, cfg.num_heads, ctx.kernel_backend
    q = L.matmul(h, bp["wq"], kb).reshape(B, S, H, m.qk_head_dim)
    kv = L.matmul(h, bp["wkv_a"], kb)
    c = L.rms_norm(kv[..., :m.kv_lora_rank], bp["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(kv[..., None, m.kv_lora_rank:], positions,
                      cfg.rope_theta)[:, :, 0]
    q_pe = rope_pairs(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q[..., :m.qk_nope_head_dim], q_pe, jnp.concatenate([c, k_pe], -1)


def _attend_decompressed(bp, q_nope, q_pe, latent, cfg: ModelConfig,
                         ctx: Ctx):
    """Causal attention of a sequence over its own latents, keys and values
    decompressed through ``wkv_b``.  Returns (B, S, H * v)."""
    B, S = latent.shape[:2]
    m, H = cfg.mla, cfg.num_heads
    kv = L.matmul(latent[..., :m.kv_lora_rank], bp["wkv_b"],
                  ctx.kernel_backend)
    kv = kv.reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_pe = jnp.broadcast_to(latent[:, :, None, m.kv_lora_rank:],
                            (B, S, H, m.qk_rope_head_dim))
    k = jnp.concatenate([kv[..., :m.qk_nope_head_dim], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    # the chunked attention keeps one head width for q, k and v: v rides
    # zero-padded to the query width and the padding is cut off after
    v = kv[..., m.qk_nope_head_dim:]
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, m.qk_head_dim - m.v_head_dim),))
    o = L.flash_attention(q, k, v, causal=True, chunk=ctx.attn_chunk,
                          scale=m.qk_head_dim ** -0.5, backend="xla")
    return o[..., :m.v_head_dim].reshape(B, S, H * m.v_head_dim)


def _attend_absorbed(bp, q_nope, q_pe, cache, pos, active, cfg: ModelConfig,
                     ctx: Ctx, layer=None):
    """One query token per slot over the latent cache (B, S_max, D), or
    layer ``layer`` of the stacked leaf (L, B, S_max, D), in the absorbed
    form.  Returns (B, 1, H * v)."""
    m, H = cfg.mla, cfg.num_heads
    B = q_nope.shape[0]
    dt = q_nope.dtype
    if "w_uk" in bp:
        w_uk, w_uv = bp["w_uk"], bp["w_uv"]
    else:
        w_uk, w_uv = absorbed_weights(bp["wkv_b"], cfg, dt)
    q_lat = jnp.einsum("bhn,hnr->bhr", q_nope[:, 0], w_uk.astype(dt))
    q = jnp.concatenate([q_lat, q_pe[:, 0]], -1)              # (B, H, D)
    scale = m.qk_head_dim ** -0.5
    kv_len = pos + 1
    if L.resolve_backend(ctx.kernel_backend) == "pallas":
        from repro.kernels.ops import decode_attention_op
        o_lat = decode_attention_op(
            q[:, None], cache, None, kv_len=kv_len, q_pos=pos,
            active=active, layer=layer, scale=scale,
            dv=m.kv_lora_rank)[:, 0]
    else:
        if layer is not None:
            cache = cache[layer]
        s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                       cache.astype(jnp.float32)) * scale
        k_pos = jnp.arange(cache.shape[1])
        s = jnp.where(k_pos[None, None, :] < kv_len[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", p,
                           cache[..., :m.kv_lora_rank].astype(jnp.float32))
        if active is not None:
            o_lat = jnp.where(active[:, None, None], o_lat, 0.0)
    o = jnp.einsum("bhr,hrv->bhv", o_lat.astype(dt), w_uv.astype(dt))
    return o.reshape(B, 1, H * m.v_head_dim)


def _write_latent(cache, latent, pos):
    """Insert a prompt's latent rows (B, S_new, D) into one layer's
    ``cache`` (B, S_max, D) at ``pos`` (B,).  A decode step's one row a
    slot goes into the stacked leaf in place instead (:func:`block` with
    ``layer``)."""
    B, S_new = latent.shape[:2]
    idx = pos[:, None] + jnp.arange(S_new)[None, :]
    return cache.at[jnp.arange(B)[:, None], idx].set(latent.astype(cache.dtype))


# --------------------------------------------------------------------------
# feed-forward: dense SwiGLU and the dropless expert layer
# --------------------------------------------------------------------------

def _swiglu(p, h, ctx: Ctx):
    kb = ctx.kernel_backend
    a = jax.nn.silu(L.matmul(h, p["w_gate"], kb)) * L.matmul(h, p["w_up"], kb)
    if ctx.act_bits:
        a = L.fake_quant_act(a, ctx.act_bits)
    return L.matmul(a, p["w_down"], kb)


def route(x2d, router, bias, cfg: ModelConfig):
    """(expert ids (T, k) int32, weights (T, k) float32): the top ``k`` of
    ``sigmoid(x W_r) + bias``, weighted by their sigmoids normalised to sum
    1, times ``routed_scaling``."""
    e = cfg.moe
    s = jax.nn.sigmoid(jnp.dot(x2d.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), e.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * e.routed_scaling
    return idx.astype(jnp.int32), w


def row_tile(rows: int, experts: int) -> int:
    """Rows of one tile of the grouped matmul: the power of two at or above
    the mean rows an expert gets, between 16 (a bf16 sublane tile) and 128
    (the MXU's height on a v5e)."""
    mean = -(-rows // experts)
    return min(128, max(16, 1 << (mean - 1).bit_length()))


@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """Where each (token, choice) pair sits in the expert-sorted rows.

    Expert ``e``'s rows occupy ``[start_e, start_e + count_e)`` of a layout
    of ``num_tiles * tm`` rows, its block padded to whole tiles of ``tm``
    rows.  ``tile_expert[t]`` is the expert of tile ``t`` for the
    ``n_tiles`` real tiles and repeats the last one after them;
    ``dest`` (T * k,) is each pair's row, ``rows`` for a pair routed
    nowhere."""
    tm: int
    rows: int
    dest: jax.Array
    tile_expert: jax.Array
    n_tiles: jax.Array
    counts: jax.Array


def group_layout(idx, valid, num_experts: int) -> GroupLayout:
    """The sorted, tile-padded layout of ``idx`` (T, k); pairs of tokens
    with ``valid`` False are routed to no expert.  One-hot counts and
    running counts place every pair without a sort: a pair's row is its
    expert's block start plus the pairs before it of the same expert."""
    T, k = idx.shape
    R, E = T * k, num_experts
    tm = row_tile(R, E)
    num_tiles = -(-R // tm) + min(E, R)
    flat = idx.reshape(-1)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, E)
    onehot = (flat[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * tm                    # padded block starts
    before = jnp.cumsum(onehot, axis=0) - onehot       # same-expert pairs ahead
    dest = jnp.sum(onehot * (start[None, :] + before), axis=1)
    dest = jnp.where(flat < E, dest, num_tiles * tm)
    n_tiles = tile_end[-1]
    t = jnp.minimum(jnp.arange(num_tiles, dtype=jnp.int32),
                    jnp.maximum(n_tiles - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), E - 1)
    return GroupLayout(tm=tm, rows=num_tiles * tm,
                       dest=dest.astype(jnp.int32),
                       tile_expert=tile_expert.astype(jnp.int32),
                       n_tiles=n_tiles.reshape(1).astype(jnp.int32),
                       counts=counts)


def moe_ffn(mp, h, cfg: ModelConfig, ctx: Ctx, valid=None):
    """Expert layer over h (B, S, d); returns (out, record): the record
    holds :data:`STEP_COUNTERS` (int32 scalars: experts with a row, the
    largest expert's rows) and the experts each token was routed to
    (``EXPERTS``, (B, S, top_k) int32)."""
    B, S, d = h.shape
    x2d = h.reshape(B * S, d)
    idx, w = route(x2d, mp["router"], mp["bias"], cfg)
    lay = group_layout(idx, valid, cfg.moe.num_experts)
    k = idx.shape[1]
    xs = jnp.zeros((lay.rows, d), x2d.dtype).at[lay.dest].set(
        jnp.repeat(x2d, k, axis=0), mode="drop")
    kb = ctx.kernel_backend
    a = (jax.nn.silu(L.grouped_matmul(xs, mp["w_gate"], lay, kb))
         * L.grouped_matmul(xs, mp["w_up"], lay, kb))
    if ctx.act_bits:
        a = L.fake_quant_act(a, ctx.act_bits)
    ys = L.grouped_matmul(a, mp["w_down"], lay, kb)
    per = jnp.take(ys, lay.dest, axis=0, mode="fill", fill_value=0)
    y = jnp.einsum("tk,tkd->td", w, per.reshape(B * S, k, d)
                   .astype(jnp.float32)).astype(h.dtype)
    y = y + _swiglu(mp["shared"], x2d, ctx)
    record = {"experts_touched": jnp.sum(lay.counts > 0).astype(jnp.int32),
              "largest_group": jnp.max(lay.counts).astype(jnp.int32),
              EXPERTS: idx.reshape(B, S, k)}
    return y.reshape(B, S, d), record


# --------------------------------------------------------------------------
# one decoder layer (also the unit TesseraQ reconstructs)
# --------------------------------------------------------------------------

def block(bp: dict, x, cfg: ModelConfig, ctx: Ctx = DEFAULT_CTX, *,
          positions, cache=None, pos=None, active=None, layer=None):
    """One layer over x (B, S, d).  Without ``cache``: causal attention over
    x itself.  With ``cache`` (B, S_max, D) and ``pos`` (B,): a prefill
    from position 0 that writes its latents at ``pos`` (a one-token prompt
    attends in the absorbed form, over the cache).  With ``layer``
    (int32 scalar), ``cache`` is the stacked leaf (L, B, S_max, D) and x one
    token a slot: a decode step that writes each slot's latent row in place
    at ``(layer, b, pos[b])`` (``pos == S_max`` writes nothing) and attends
    in the absorbed form.  Returns (x, cache, record), the expert layer's
    record (:func:`moe_ffn`), None for a dense layer."""
    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    if ctx.act_bits:
        h = L.fake_quant_act(h, ctx.act_bits)
    q_nope, q_pe, latent = _project(bp, h, cfg, ctx, positions)
    if layer is not None:
        cache = write_rows(cache, layer, latent[:, 0], pos,
                           ctx.kernel_backend)
    elif cache is not None:
        cache = _write_latent(cache, latent, pos)
    if cache is not None and x.shape[1] == 1:
        o = _attend_absorbed(bp, q_nope, q_pe, cache, pos, active, cfg, ctx,
                             layer)
    else:
        o = _attend_decompressed(bp, q_nope, q_pe, latent, cfg, ctx)
    if ctx.act_bits:
        o = L.fake_quant_act(o, ctx.act_bits)
    x = x + L.matmul(o, bp["wo"], ctx.kernel_backend)
    h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if ctx.act_bits:
        h = L.fake_quant_act(h, ctx.act_bits)
    record = None
    if "moe" in bp:
        f, record = moe_ffn(bp["moe"], h, cfg, ctx, valid=active)
    else:
        f = _swiglu(bp, h, ctx)
    return x + f, cache, record


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------

def _unembed(params, cfg: ModelConfig, x, ctx: Ctx):
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return L.matmul(x, params["head"], ctx.kernel_backend)


def forward(params, cfg: ModelConfig, tokens, ctx: Ctx = DEFAULT_CTX):
    """Logits (B, S, V) without a cache."""
    x = params["embed"][tokens]
    positions = jnp.arange(x.shape[1])

    def step(h, bp):
        h, _, _ = block(bp, h, cfg, ctx, positions=positions)
        return h, ()

    for key, _ in STACKS:
        x, _ = layer_loop(maybe_remat(step, ctx), x, params[key],
                          cfg.unroll_layers)
    return _unembed(params, cfg, x, ctx)


def loss_fn(params, cfg: ModelConfig, batch, ctx: Ctx = DEFAULT_CTX):
    tokens = batch["tokens"]
    logits = forward(params, cfg, tokens[:, :-1], ctx).astype(jnp.float32)
    targets = tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def _no_pages(ctx: Ctx, ptab):
    if ptab is not None or ctx.page_size:
        raise NotImplementedError(
            "latent attention serves from the dense store only: its "
            "CacheSpec is not pageable")


def prefill(params, cfg: ModelConfig, tokens, cache, ctx: Ctx = DEFAULT_CTX,
            *, start_pos=0, ptab=None):
    """Whole-prompt prefill from position 0.  Returns (last logits, cache,
    record): ``record["token"][EXPERTS]``, (expert layers, B, S, top_k)
    int32, the experts each prompt token was routed to."""
    _no_pages(ctx, ptab)
    if not isinstance(start_pos, int) or start_pos:
        raise NotImplementedError(
            "latent attention prefills whole prompts only: its CacheSpec is "
            "not chunkable")
    x = params["embed"][tokens]
    B, S = x.shape[:2]
    positions = jnp.arange(S)
    pos0 = jnp.zeros((B,), jnp.int32)

    def step(h, layer):
        bp, c = layer
        h, c, record = block(bp, h, cfg, ctx, positions=positions, cache=c,
                             pos=pos0)
        return h, (c, None if record is None else record[EXPERTS])

    new, experts = {}, None
    for key, leaf in STACKS:
        x, (new[leaf], experts) = layer_loop(
            step, x, (params[key], cache[leaf]), cfg.unroll_layers)
    return (_unembed(params, cfg, x[:, -1:], ctx)[:, 0], new,
            {"token": {EXPERTS: experts}})


def decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                ctx: Ctx = DEFAULT_CTX, *, active=None, ptab=None):
    """One decode step.  Returns (logits, cache, record): ``record["step"]``
    holds :data:`STEP_COUNTERS`, (expert layers,) int32 each, and
    ``record["token"][EXPERTS]``, (expert layers, B, top_k) int32, the
    experts each slot's token was routed to.  Each of :data:`STACKS`
    carries its own stacked leaf through its layer loop, written in place
    one row a slot (:func:`~repro.models.common.cache_layer_loop`)."""
    _no_pages(ctx, ptab)
    x = params["embed"][tokens][:, None, :]

    def step(h, c, i, bp):
        return block(bp, h, cfg, ctx, positions=pos[:, None], cache=c,
                     pos=pos, active=active, layer=i)

    new, record = {}, None
    for key, leaf in STACKS:
        x, new[leaf], record = cache_layer_loop(
            step, x, cache[leaf], params[key], cfg.unroll_layers)
    record = {"step": {k: record[k] for k in STEP_COUNTERS},
              "token": {EXPERTS: record[EXPERTS][:, :, 0]}}
    return _unembed(params, cfg, x, ctx)[:, 0], new, record
