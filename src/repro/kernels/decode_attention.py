"""Pallas TPU kernel: slot-aware single-token decode attention.

The continuous-batching scheduler keeps its KV cache slot-major on axis 1
of every cache leaf (``models/common.CACHE_SLOT_AXIS``) and tracks which
slots are live in an occupancy vector.  The XLA decode fast path computes
dense (slots, heads, max_seq) scores and masks post-hoc — every retired or
empty slot still pays full attention FLOPs and full cache reads.

This kernel reads the cache-lane layout directly (k/v blocks are indexed
``(b, c, 0, 0)`` straight into the (slots, S, Hkv, D) cache — no transpose,
no copy) and makes the occupancy vector and ragged per-slot lengths part of
the kernel contract:

  * ``active``: inactive slots skip ALL compute via ``@pl.when`` and emit
    zeros (their accumulator never initializes past zero);
  * ``kv_len``: K chunks entirely past a slot's ragged length are skipped,
    so a slot at position 7 in a 4096-lane cache touches one chunk, not 32;
  * online softmax (running max / sum in VMEM scratch) over the chunked K
    axis, so max_seq never has to fit in one VMEM tile.

Per-(slot, head) compute is a pure function of that slot's own lanes, which
preserves the scheduler's bit-identity contract (scheduled tokens ==
serving the request alone at the same max_seq).

q layout: (B, Hkv, G, D) — GQA query groups folded next to their KV head.
k/v: (B, S, Hkv, D), the scheduler's native cache layout.  One program
handles one (slot, chunk) and every KV head of it: a k/v block is
``(chunk, Hkv, D)``, whose last two dims span the whole array as the chip's
tiling requires, and the heads are walked inside the kernel.  The per-slot
scalars (``kv_len``, ``q_pos``, ``active``) ride in as scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_attn_kernel(len_ref, pos_ref, act_ref, q_ref, k_ref, v_ref,
                        o_ref, m_ref, l_ref, acc_ref, *,
                        csz: int, nc: int, scale: float):
    """Online softmax of one slot over chunk ``c`` of its cache, for every
    KV head.  The dense and paged kernels differ only in which block the
    index maps fetch, so they share this body."""
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    q_pos = pos_ref[b]

    @pl.when((act_ref[b] > 0) & (c * csz < kv_len))
    def _chunk():
        kpos = c * csz + jax.lax.broadcasted_iota(jnp.int32, (1, csz), 1)
        live = (kpos < kv_len) & (kpos <= q_pos)
        for h in range(q_ref.shape[0]):
            q = q_ref[h].astype(jnp.float32) * scale           # (G, D)
            kb = k_ref[:, h, :].astype(jnp.float32)            # (csz, D)
            vb = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_ref[h, :, :1]                           # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h, :, :1] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(c == nc - 1)
    def _done():
        # inactive slots never accumulate: l == 0, acc == 0 -> output zeros
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_attn_kernel(ptab_ref, *refs, **kw):
    # the page table is consumed by the k/v index maps only
    _decode_attn_kernel(*refs, **kw)


def _attend(kernel, q, k, v, scalars, kv_index, *, csz: int, nc: int,
            interpret: bool):
    """The pallas_call both decode kernels share: grid (slots, chunks),
    ``scalars`` as scalar prefetch, k/v blocks of ``(csz, Hkv, D)`` placed
    by ``kv_index``."""
    B, Hkv, G, D = q.shape
    q_spec = pl.BlockSpec((None, Hkv, G, D), lambda b, c, *_: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, csz, Hkv, D), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nc),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running max (col 0 live)
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running sum (col 0 live)
            pltpu.VMEM((Hkv, G, D), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, q, k, v)


def _slot_scalars(B, kv_len, q_pos, active):
    act = (jnp.ones((B,), jnp.int32) if active is None
           else active.astype(jnp.int32))
    return kv_len.astype(jnp.int32), q_pos.astype(jnp.int32), act


def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           ptab: jax.Array, *,
                           kv_len: jax.Array, q_pos: jax.Array,
                           active: jax.Array | None = None,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Single-token decode attention over a paged KV pool.

    q: (B, Hkv, G, D).  k_pool/v_pool: (P, psz, Hkv, D) page pools.
    ptab: (B, W) int32 page table — logical chunk c of slot b lives in pool
    page ``ptab[b, c]``; W * psz == max_seq.  The page table rides in as a
    scalar-prefetch operand so the k/v block index maps can chase it: the
    grid's chunk axis walks LOGICAL positions while the blocks fetched are
    whichever physical pages the table names.  Unallocated table entries
    (page 0) are loaded but fully masked by ``kv_len``, which keeps the
    online softmax bit-identical to the dense kernel at chunk == psz.

    kv_len/q_pos: (B,) int32; active: (B,) occupancy or None for all-live.
    Returns (B, Hkv, G, D) in q.dtype; rows of inactive slots are zero.
    """
    B, Hkv, G, D = q.shape
    P, psz = k_pool.shape[0], k_pool.shape[1]
    W = ptab.shape[1]
    if k_pool.shape != (P, psz, Hkv, D) or v_pool.shape != (P, psz, Hkv, D):
        raise ValueError(f"pool layout mismatch: q {q.shape} vs "
                         f"k {k_pool.shape} / v {v_pool.shape}; under "
                         "tensor-parallel serving Hkv is the SHARD-local "
                         "KV-head count — the pools shard over heads with "
                         "q while the page table stays replicated, so a "
                         "mismatch means the cache specs and the param "
                         "plan disagree (launch.sharding.ServeSpec)")
    if ptab.shape != (B, W):
        raise ValueError(f"ptab {ptab.shape} is not (B={B}, W)")
    scale = float(D) ** -0.5 if scale is None else scale
    kernel = functools.partial(_paged_decode_attn_kernel, csz=psz, nc=W,
                               scale=scale)
    return _attend(kernel, q, k_pool, v_pool,
                   (ptab.astype(jnp.int32),
                    *_slot_scalars(B, kv_len, q_pos, active)),
                   lambda b, c, ptab_ref, *_: (ptab_ref[b, c], 0, 0, 0),
                   csz=psz, nc=W, interpret=interpret)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: jax.Array, q_pos: jax.Array,
                     active: jax.Array | None = None,
                     scale: float | None = None, chunk: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, D); k/v: (B, S, Hkv, D) — the scheduler cache layout,
    slot dim on axis B(=0 here, axis 1 of the stacked cache), consumed
    without transposition.  kv_len/q_pos: (B,) int32 ragged per-slot valid
    length and query position.  active: (B,) bool occupancy, or None for
    all-live (lockstep serving).

    Returns (B, Hkv, G, D) in q.dtype; rows of inactive slots are zero.
    """
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    if k.shape != (B, S, Hkv, D) or v.shape != (B, S, Hkv, D):
        raise ValueError(f"cache-lane layout mismatch: q {q.shape} vs "
                         f"k {k.shape} / v {v.shape}; under tensor-parallel "
                         "serving Hkv is the SHARD-local KV-head count — "
                         "cache lanes shard over heads with q, so a "
                         "mismatch means the cache specs and the param "
                         "plan disagree (launch.sharding.ServeSpec)")
    scale = float(D) ** -0.5 if scale is None else scale
    csz = min(chunk, S)
    nc = pl.cdiv(S, csz)
    kernel = functools.partial(_decode_attn_kernel, csz=csz, nc=nc,
                               scale=scale)
    return _attend(kernel, q, k, v, _slot_scalars(B, kv_len, q_pos, active),
                   lambda b, c, *_: (b, c, 0, 0),
                   csz=csz, nc=nc, interpret=interpret)
