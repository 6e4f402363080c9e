"""Shared neural building blocks: norms, RoPE, flash-style attention, matmul
dispatch over plain / quantized (QTensor) weights, per-token activation
fake-quant.

All modules are pure functions over param dicts; weights use the convention
``(in_features, out_features)`` (experts: ``(E, in, out)``).

QTensor matmuls dispatch per call on an explicit ``backend`` argument
(plumbed from ``Ctx.kernel_backend`` by every model family): "xla" unpacks
and runs a dense matmul, "pallas" runs the fused dequant-matmul kernel.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor, qmatmul


# --------------------------------------------------------------------------
# matmul dispatch (the single entry point the quantizer swaps weights under)
# --------------------------------------------------------------------------

KERNEL_BACKENDS = ("xla", "pallas")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PsumWeight:
    """Marker wrapper for an input-channel-sharded weight inside shard_map.

    The serve-time TP contract (``launch.sharding.ServeSpec``) splits
    in-split linears (wo/w_down/cv) over their reduction dim; each shard's
    partial matmul must be ``psum``'d over ``axis`` before anything nonlinear
    consumes it.  Wrapping the weight keeps the family forwards free of
    sharding logic: :func:`matmul` unwraps, multiplies the LOCAL shard, and
    reduces — the one place the in-channel epilogue lives.  Registered as a
    pytree (``axis`` is static aux) so wrapped weights flow through the
    layer scan / ``take_layer`` like any stacked weight."""
    w: Any
    axis: str

    def tree_flatten(self):
        return ((self.w,), self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


def resolve_backend(backend: Optional[str]) -> str:
    """Resolve the QTensor matmul backend for ONE dispatch.

    ``backend`` comes from the caller (``Ctx.kernel_backend``, plumbed from
    ``QuantConfig.kernel_backend``); ``None`` falls back to the
    ``REPRO_KERNEL_BACKEND`` env var — read fresh at trace time, never cached
    in module state — and then to "xla"."""
    if backend is None:
        import os
        backend = os.environ.get("REPRO_KERNEL_BACKEND", "xla")
    if backend not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {KERNEL_BACKENDS}")
    return backend


def matmul(x: jax.Array, w, backend: Optional[str] = None) -> jax.Array:
    if isinstance(w, PsumWeight):
        return jax.lax.psum(matmul(x, w.w, backend), w.axis)
    if isinstance(w, QTensor):
        if resolve_backend(backend) == "pallas":
            from repro.kernels.ops import qtensor_matmul
            return qtensor_matmul(x, w)
        return qmatmul(x, w)
    return x @ w


def expert_matmul(a: jax.Array, w, backend: Optional[str] = None) -> jax.Array:
    """Batched per-expert matmul: (E, C, d) x (E, d, f) -> (E, C, f)."""
    if isinstance(w, QTensor):
        if resolve_backend(backend) == "pallas":
            from repro.kernels.ops import qtensor_expert_matmul
            return qtensor_expert_matmul(a, w)
        if w.act_scale is not None:
            a = a / w.act_scale.astype(a.dtype)
        w = w.dequantize(a.dtype)
    return jnp.einsum("ecd,edf->ecf", a, w)


def grouped_matmul(x: jax.Array, w, layout, backend: Optional[str] = None
                   ) -> jax.Array:
    """Dropless grouped matmul: rows of ``x`` (rows, K) sorted by expert and
    padded per expert to whole tiles as ``layout``
    (``models.mla_moe.GroupLayout``) places them, against expert-stacked
    ``w`` (E, K, N).  Rows of tiles past the real ones come back
    unspecified; callers read only the rows the layout gives a pair.

    The pallas backend runs the ``quant_gmm`` kernel.  Otherwise each tile
    is multiplied by its expert's weight, gathered per tile: a plain batched
    matmul that ``vmap`` and ``grad`` (the reconstruction engine) go
    through."""
    if isinstance(w, QTensor):
        if resolve_backend(backend) == "pallas":
            from repro.kernels.ops import qtensor_gmm
            return qtensor_gmm(x, w, layout.tile_expert, layout.n_tiles,
                               row_tile=layout.tm)
        if w.act_scale is not None:
            x = x / w.act_scale.astype(x.dtype)
        w = w.dequantize(x.dtype)
    xt = x.reshape(-1, layout.tm, x.shape[-1])
    wt = jnp.take(w.astype(x.dtype), layout.tile_expert, axis=0)
    return jnp.einsum("tmk,tkn->tmn", xt, wt).reshape(x.shape[0], -1)


def fake_quant_act(x: jax.Array, bits: int, symmetric: bool = True) -> jax.Array:
    """Per-token dynamic activation quantization (simulated).

    Quantizes over the last dim per token; straight-through in the sense that
    it is only used in inference paths (no gradient needed).
    """
    qmax = (1 << bits) - 1
    xf = x.astype(jnp.float32)
    if symmetric:
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / ((qmax - 1) / 2)
        q = jnp.clip(jnp.round(xf / scale), -(qmax + 1) // 2, qmax // 2)
        return (q * scale).astype(x.dtype)
    lo = jnp.min(xf, axis=-1, keepdims=True)
    hi = jnp.max(xf, axis=-1, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zero = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(xf / scale) + zero, 0, qmax)
    return ((q - zero) * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# norms / embeddings / positional
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, g: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def layer_norm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D), positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs       # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# --------------------------------------------------------------------------
# flash attention: online softmax over KV chunks, O(chunk) memory, with a
# FlashAttention-2 style custom backward (recompute scores per chunk) so the
# scan does not checkpoint O(Sq x D) residuals per step — this is what keeps
# 32k-token training under the HBM budget (EXPERIMENTS.md §Dry-run).
# --------------------------------------------------------------------------

def _mask_for(idx, csz, q_pos, valid_len, causal, prefix_len):
    k_pos = idx * csz + jnp.arange(csz, dtype=jnp.float32)
    mask = k_pos[None, None, None, None, :] < valid_len[:, None, None, None, None]
    if causal:
        cm = k_pos[None, None, None, None, :] <= q_pos[:, None, None, :, None]
        if prefix_len is not None:
            # prefix-LM (paligemma): the image/prompt prefix attends fully
            cm = cm | (k_pos[None, None, None, None, :] < prefix_len)
        mask = mask & cm
    return mask


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, q_pos, valid_len, causal, prefix_len, chunk, scale):
    out, _ = _flash_fwd(q, k, v, q_pos, valid_len, causal, prefix_len,
                        chunk, scale)
    return out


def _flash_fwd(q, k, v, q_pos, valid_len, causal, prefix_len, chunk, scale):
    """q: (B,Hkv,G,Sq,D) f32*scale applied; k,v: (N,B,Hkv,C,D)."""
    B, Hkv, G, Sq, D = q.shape
    csz = k.shape[3]

    def step(carry, kv):
        m, l, acc, idx = carry
        kb, vb = kv
        s = jnp.einsum("bhgqd,bhcd->bhgqc", q, kb.astype(jnp.float32))
        mask = _mask_for(idx, csz, q_pos, valid_len, causal, prefix_len)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new, idx + 1), ()

    m0 = jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)), (k, v))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


def _flash_core_fwd(q, k, v, q_pos, valid_len, causal, prefix_len, chunk,
                    scale):
    out, lse = _flash_fwd(q, k, v, q_pos, valid_len, causal, prefix_len,
                          chunk, scale)
    return out, (q, k, v, q_pos, valid_len, out, lse)


def _flash_core_bwd(causal, prefix_len, chunk, scale, res, dout):
    q, k, v, q_pos, valid_len, out, lse = res
    csz = k.shape[3]
    delta = jnp.sum(dout * out, axis=-1)                       # (B,Hkv,G,Sq)

    def step(dq, kvi):
        kb, vb, idx = kvi
        kf, vf = kb.astype(jnp.float32), vb.astype(jnp.float32)
        s = jnp.einsum("bhgqd,bhcd->bhgqc", q, kf)
        mask = _mask_for(idx, csz, q_pos, valid_len, causal, prefix_len)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dvb = jnp.einsum("bhgqc,bhgqd->bhcd", p, dout)
        dp = jnp.einsum("bhgqd,bhcd->bhgqc", dout, vf)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhgqc,bhcd->bhgqd", ds, kf)
        dkb = jnp.einsum("bhgqc,bhgqd->bhcd", ds, q)
        return dq, (dkb.astype(kb.dtype), dvb.astype(vb.dtype))

    idxs = jnp.arange(k.shape[0], dtype=jnp.int32)
    dq0 = jnp.zeros_like(q)
    dq, (dk, dv) = jax.lax.scan(step, dq0, (k, v, idxs))
    return (dq, dk, dv, jnp.zeros_like(q_pos), jnp.zeros_like(valid_len))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    q_offset=0,
                    kv_len: Optional[jax.Array] = None,
                    chunk: int = 512,
                    scale: Optional[float] = None,
                    prefix_len: Optional[int] = None,
                    backend: Optional[str] = None,
                    active: Optional[jax.Array] = None,
                    pages: Optional[tuple] = None,
                    layer: Optional[jax.Array] = None) -> jax.Array:
    """Chunked attention with GQA support.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (scalar or (B,)) for causal masks
    during decode.  ``kv_len``: (B,) valid KV length (cache masking).
    ``backend``: kernel backend for the Sq == 1 decode step — "pallas"
    dispatches the slot-aware decode kernel, which reads the cache-lane
    layout directly and skips inactive slots via ``active`` ((B,) occupancy,
    None = all live) and the ragged ``kv_len`` instead of masking post-hoc.
    Inactive rows come back zero.

    ``pages = (ptab, page_size)`` marks k/v as page POOLS (num_pages,
    page_size, Hkv, D) indexed by the (B, W) page table ``ptab``.  The
    pallas decode step walks the table inside the kernel (no gather); every
    other path gathers the virtual slot-major cache — shaped exactly like
    the dense lane, W*page_size == Sk — and proceeds unchanged, which is
    what makes paged attention bit-identical to dense.

    ``layer`` (int32 scalar) marks k/v as the stacked cache leaves (L, B,
    Sk, Hkv, D): the pallas decode step reads layer ``layer`` of them in
    place, every other path slices that layer out first.
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[-2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    if pages is not None:
        ptab, page_size = pages
        if (Sq == 1 and causal and kv_len is not None and prefix_len is None
                and resolve_backend(backend) == "pallas"):
            from repro.kernels.ops import paged_decode_attention_op
            q_pos = jnp.broadcast_to(
                jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
            out = paged_decode_attention_op(
                q.reshape(B, Hkv, G, D), k, v, ptab, kv_len=kv_len,
                q_pos=q_pos, active=active, scale=scale)
            return out.reshape(B, Sq, Hq, D).astype(q.dtype)
        from repro.models.common import gather_pages
        k = gather_pages(k, ptab)
        v = gather_pages(v, ptab)

    if (Sq == 1 and causal and kv_len is not None and prefix_len is None
            and resolve_backend(backend) == "pallas"):
        from repro.kernels.ops import decode_attention_op
        q_pos = jnp.broadcast_to(
            jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
        out = decode_attention_op(q.reshape(B, Hkv, G, D), k, v,
                                  kv_len=kv_len, q_pos=q_pos, active=active,
                                  layer=layer, scale=scale, chunk=chunk)
        return out.reshape(B, Sq, Hq, D).astype(q.dtype)
    if layer is not None:
        k, v = k[layer], v[layer]
    Sk = k.shape[1]

    qf = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32) * scale
    qf = qf.transpose(0, 2, 3, 1, 4)                           # (B,Hkv,G,Sq,D)

    if Sq == 1:
        # decode fast path: single score row, no chunk reshape/transpose of
        # the (large, sharded) cache — GSPMD partitions the softmax over a
        # sequence-sharded cache with two small psums (§Perf iteration A3)
        q_pos1 = jnp.asarray(q_offset, jnp.float32).reshape(-1)[:, None]
        q_pos1 = jnp.broadcast_to(q_pos1, (B, 1))
        valid1 = (kv_len.astype(jnp.float32) if kv_len is not None
                  else jnp.full((B,), float(Sk), jnp.float32))
        s = jnp.einsum("bhgqd,bshd->bhgqs", qf, k.astype(jnp.float32))
        k_pos = jnp.arange(Sk, dtype=jnp.float32)
        mask = k_pos[None, None, None, None, :] < valid1[:, None, None, None, None]
        if causal:
            cm = (k_pos[None, None, None, None, :]
                  <= q_pos1[:, None, None, :, None])
            if prefix_len is not None:
                cm = cm | (k_pos[None, None, None, None, :] < prefix_len)
            mask = mask & cm
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqs,bshd->bhgqd", p, v.astype(jnp.float32))
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
        return out.astype(q.dtype)

    csz = min(chunk, Sk)
    pad = (-Sk) % csz
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Skp = k.shape[1]
    kc = k.reshape(B, Skp // csz, csz, Hkv, D).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, Skp // csz, csz, Hkv, D).transpose(1, 0, 3, 2, 4)

    q_pos = jnp.asarray(q_offset, jnp.float32)[..., None] + jnp.arange(
        Sq, dtype=jnp.float32)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    q_pos = jnp.broadcast_to(q_pos, (B, Sq))
    valid_len = (kv_len.astype(jnp.float32) if kv_len is not None
                 else jnp.full((B,), float(Sk), jnp.float32))

    out = _flash_core(qf, kc, vc, q_pos, valid_len, causal, prefix_len,
                      csz, scale)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)
