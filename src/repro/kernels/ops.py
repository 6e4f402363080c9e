"""Jit'd public wrappers around the Pallas kernels, with padding/shape glue
and a backend switch (``interpret=True`` on CPU, compiled on TPU).

``qtensor_matmul`` is the drop-in QTensor consumer used by the serving path
when ``REPRO_KERNEL_BACKEND=pallas`` (the XLA unpack path in
core/qtensor.qmatmul is the default on CPU)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.qtensor import PACK_FACTOR, QTensor
from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.quant_gemv import quant_gemv
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_experts
from repro.kernels.soft_round import soft_round

# decode batches (M = live slots) at or below this row count dispatch to the
# decode-shaped GEMV kernel instead of the prefill-tiled matmul
DECODE_GEMV_MAX_ROWS = 32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_rows_to(x, target, axis=0):
    """Zero-pad ``axis`` up to exactly ``target`` entries."""
    cur = x.shape[axis]
    if cur == target:
        return x
    assert cur < target, (cur, target)
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bits", "group_size",
                                             "block_m", "block_n", "block_k"))
def quant_matmul_op(x, packed, scale, zero, *, bits: int, group_size: int,
                    block_m=256, block_n=256, block_k=512):
    """Shape-gluing wrapper: pads M/N/K to tile multiples, trims after.

    K padding covers EVERY K-keyed operand consistently: x columns, packed
    rows (K // pack_factor) and scale/zero rows (K // group_size) all grow
    to the same padded K.  The padded region is harmless — x is zero there,
    so whatever the zero bytes dequantize to is multiplied away.
    """
    M, K = x.shape
    N = packed.shape[1]
    ppb = PACK_FACTOR[bits]
    # no row-floor: callers this small belong on the decode GEMV (see
    # qtensor_matmul), and padding 1..7 live rows up to 8 just burns MXU rows
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk, Kp = _snap_block_k(block_k, K, group_size, ppb, bits)
    xp = _pad_to(_pad_rows_to(x, Kp, axis=1), bm, 0)
    out = quant_matmul(xp,
                       _pad_to(_pad_rows_to(packed, Kp // ppb), bn, 1),
                       _pad_to(_pad_rows_to(scale, Kp // group_size), bn, 1),
                       _pad_to(_pad_rows_to(zero, Kp // group_size), bn, 1),
                       bits=bits, group_size=group_size,
                       block_m=bm, block_n=bn, block_k=bk,
                       interpret=_interpret())
    return out[:M, :N]


def _snap_block_k(block_k, K, group_size, ppb, bits):
    """Snap bk to the kernel's group-alignment contract and return the
    padded K every K-keyed operand must grow to."""
    bk = min(block_k, K)
    if bk % group_size and group_size % bk:
        # snap bk so the group-alignment contract holds: down to a whole
        # number of groups when groups are smaller than the tile, otherwise
        # to a divisor of the (larger) group
        bk = ((bk // group_size) * group_size if bk > group_size
              else math.gcd(bk, group_size))
    gpt = bk // group_size
    if gpt > 1 and gpt % 8 and 8 % gpt:
        # whole groups per tile must be a divisor or a multiple of 8 so the
        # kernels can slice their scale rows at sublane-aligned offsets
        bk = group_size << (gpt.bit_length() - 1)
    # after the snaps one of (bk, group_size) divides the other, so their
    # max is their lcm: pad K to it and both the tile grid and the group
    # rows stay aligned
    align = max(bk, group_size)
    Kp = K + (-K) % align
    if Kp % ppb:
        raise ValueError(f"padded K={Kp} not divisible by the bit-packing "
                         f"factor {ppb} (bits={bits}); under tensor-parallel "
                         "serving K is the SHARD-local reduction dim — an "
                         "in-channel split must hand every shard whole "
                         "packed rows (launch.sharding.serve_plan only "
                         "shards when (K/ppb) % tp == 0)")
    return bk, Kp


@functools.partial(jax.jit, static_argnames=("bits", "group_size",
                                             "block_n", "block_k"))
def quant_gemv_op(x, packed, scale, zero, *, bits: int, group_size: int,
                  block_n=128, block_k=256):
    """Decode-shaped wrapper: M (the live-slot count) is NEVER padded; only
    N and K grow to tile multiples, with the same all-K-keyed-operands
    padding contract as quant_matmul_op."""
    M, K = x.shape
    N = packed.shape[1]
    ppb = PACK_FACTOR[bits]
    bn = min(block_n, N)
    bk, Kp = _snap_block_k(block_k, K, group_size, ppb, bits)
    out = quant_gemv(_pad_rows_to(x, Kp, axis=1),
                     _pad_to(_pad_rows_to(packed, Kp // ppb), bn, 1),
                     _pad_to(_pad_rows_to(scale, Kp // group_size), bn, 1),
                     _pad_to(_pad_rows_to(zero, Kp // group_size), bn, 1),
                     bits=bits, group_size=group_size,
                     block_n=bn, block_k=bk,
                     interpret=_interpret())
    return out[:, :N]


def qtensor_matmul(x: jax.Array, w: QTensor) -> jax.Array:
    """x: (..., K) bf16 x QTensor -> (..., N) via the Pallas kernels.

    Shape-based dispatch: decode-sized batches (M <= DECODE_GEMV_MAX_ROWS
    flattened rows — one token per live slot) hit the fused dequant-GEMV;
    prefill-sized batches keep the MXU-tiled quant_matmul."""
    if w.act_scale is not None:
        x = x / w.act_scale.astype(x.dtype)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    scale = w.scale.astype(jnp.float32)
    zero = w.zero.astype(jnp.float32)
    if x2.shape[0] <= DECODE_GEMV_MAX_ROWS:
        out = quant_gemv_op(x2, w.packed, scale, zero,
                            bits=w.bits, group_size=w.group_size)
    else:
        out = quant_matmul_op(x2, w.packed, scale, zero,
                              bits=w.bits, group_size=w.group_size)
    return out.reshape(*lead, w.out_features)


@functools.partial(jax.jit, static_argnames=("bits", "group_size",
                                             "block_m", "block_n", "block_k"))
def quant_matmul_experts_op(a, packed, scale, zero, *, bits: int,
                            group_size: int, block_m=256, block_n=256,
                            block_k=512):
    """Expert-batched shape glue: pads M/N/K (per-expert shapes are
    homogeneous, so padding is shared) and trims after."""
    E, M, K = a.shape
    N = packed.shape[2]
    ppb = PACK_FACTOR[bits]
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk, Kp = _snap_block_k(block_k, K, group_size, ppb, bits)
    out = quant_matmul_experts(
        _pad_to(_pad_rows_to(a, Kp, axis=2), bm, 1),
        _pad_to(_pad_rows_to(packed, Kp // ppb, axis=1), bn, 2),
        _pad_to(_pad_rows_to(scale, Kp // group_size, axis=1), bn, 2),
        _pad_to(_pad_rows_to(zero, Kp // group_size, axis=1), bn, 2),
        bits=bits, group_size=group_size,
        block_m=bm, block_n=bn, block_k=bk,
        interpret=_interpret())
    return out[:, :M, :N]


def qtensor_expert_matmul(a: jax.Array, w: QTensor) -> jax.Array:
    """Batched per-expert matmul (E, C, K) x expert-stacked QTensor
    -> (E, C, N) in ONE fused Pallas launch.

    The expert dim is folded into the kernel grid (leading parallel axis),
    so the MoE serve path issues a single pallas_call instead of one per
    expert — each expert's packed weight tile is still DMA'd exactly once."""
    if w.act_scale is not None:
        a = a / w.act_scale.astype(a.dtype)
    if a.ndim != 3 or w.packed.ndim != 3:
        raise ValueError(
            f"expected (E, C, K) activations against expert-stacked QTensor, "
            f"got a.ndim={a.ndim}, packed.ndim={w.packed.ndim}")
    return quant_matmul_experts_op(a, w.packed, w.scale.astype(jnp.float32),
                                   w.zero.astype(jnp.float32),
                                   bits=w.bits, group_size=w.group_size)


def qtensor_expert_matmul_unrolled(a: jax.Array, w: QTensor) -> jax.Array:
    """Pre-fold reference: one pallas_call per expert via a Python loop.
    Kept as the bit-parity oracle for the fused expert grid (and as a
    fallback if a backend ever rejects the 4-D grid)."""
    if w.act_scale is not None:
        a = a / w.act_scale.astype(a.dtype)
    if a.ndim != 3 or w.packed.ndim != 3:
        raise ValueError(
            f"expected (E, C, K) activations against expert-stacked QTensor, "
            f"got a.ndim={a.ndim}, packed.ndim={w.packed.ndim}")
    outs = [quant_matmul_op(a[e], w.packed[e],
                            w.scale[e].astype(jnp.float32),
                            w.zero[e].astype(jnp.float32),
                            bits=w.bits, group_size=w.group_size)
            for e in range(a.shape[0])]
    return jnp.stack(outs)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def int8_matmul_op(x_q, w_q, x_scale, w_scale, out_dtype=jnp.bfloat16):
    return int8_matmul(x_q, w_q, x_scale, w_scale, out_dtype=out_dtype,
                       interpret=_interpret())


def w4a8_matmul(x: jax.Array, w: QTensor, act_bits: int = 8) -> jax.Array:
    """Dynamic per-token activation quant + integer matmul against a QTensor.

    Asymmetric weights are recentered by 128 (exact in int8); the zero-point
    contribution is restored with the standard rank-1 correction
    ``rowsum(x_q) x (128 - zero)`` in the fp32 epilogue.  Per-channel
    weights (group_size == K) take one integer matmul; grouped weights
    accumulate one integer matmul + rank-1 correction PER GROUP (the scale
    changes along K, so the epilogue cannot be hoisted) — correct but
    ``K // group_size`` kernel launches, so per-channel is the fast path."""
    if w.packed.ndim != 2:
        raise ValueError("w4a8_matmul expects a single (non-stacked) QTensor, "
                         f"got packed.ndim={w.packed.ndim}")
    x_q, x_scale = ref.quantize_per_token_ref(x.reshape(-1, x.shape[-1]),
                                              act_bits)
    from repro.core.qtensor import unpack
    K, g = w.in_features, w.group_size
    codes = unpack(w.packed, w.bits, K, axis=-2).astype(jnp.int32)
    w_centered = (codes - 128).astype(jnp.int8)
    scale = w.scale.astype(jnp.float32)                 # (K // g, N)
    zero = w.zero.astype(jnp.float32)
    x_q_f = x_q.astype(jnp.float32)
    out = jnp.zeros((x_q.shape[0], w.out_features), jnp.float32)
    for gi in range(K // g):
        sl = slice(gi * g, (gi + 1) * g)
        part = int8_matmul_op(x_q[:, sl], w_centered[sl],
                              x_scale, scale[gi:gi + 1],
                              out_dtype=jnp.float32)
        rowsum = jnp.sum(x_q_f[:, sl], axis=-1, keepdims=True)
        corr = (rowsum * x_scale) * ((128.0 - zero[gi:gi + 1])
                                     * scale[gi:gi + 1])
        out = out + part + corr
    return out.astype(x.dtype).reshape(*x.shape[:-1], w.out_features)


def soft_round_op(base, nu, hard, v, scale, zero, *, qmax: int,
                  dst: bool = True):
    return soft_round(base, nu, hard.astype(jnp.int32), v, scale, zero,
                      qmax=qmax, dst=dst, interpret=_interpret())


# Decode attention is jitted on its own, like the matmul wrappers above, so
# that inside a decode step the kernel keeps this wrapper's name: the device
# trace shows it as ``decode_attention_op`` (``paged_decode_attention_op``)
# and the benchmark reads it by that name.
@functools.partial(jax.jit, static_argnames=("scale", "chunk"))
def decode_attention_op(q, k, v, *, kv_len, q_pos, active=None, scale=None,
                        chunk: int = 512):
    """Slot-aware decode attention (see kernels/decode_attention.py).

    q: (B, Hkv, G, D); k/v: (B, S, Hkv, D) in the scheduler's cache-lane
    layout; kv_len/q_pos: (B,); active: (B,) occupancy or None."""
    return decode_attention(q, k, v, kv_len=kv_len, q_pos=q_pos,
                            active=active, scale=scale, chunk=chunk,
                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_attention_op(q, k_pool, v_pool, ptab, *, kv_len, q_pos,
                              active=None, scale=None):
    """Paged decode attention (see kernels/decode_attention.py).

    q: (B, Hkv, G, D); k_pool/v_pool: (P, psz, Hkv, D) page pools;
    ptab: (B, W) page table; kv_len/q_pos: (B,); active: (B,) or None."""
    return paged_decode_attention(q, k_pool, v_pool, ptab, kv_len=kv_len,
                                  q_pos=q_pos, active=active, scale=scale,
                                  interpret=_interpret())
