"""Pallas TPU kernel: fused packed-weight dequantization + matmul.

This is the paper's deployment kernel (Table 8: the INT2/INT4 dequant kernel
that turns memory-bound decode into a win), adapted from its Triton/CUDA form
to the TPU memory hierarchy:

  * packed weights (uint8, ``ppb`` values per byte) are DMA'd HBM->VMEM per
    (bk x bn) tile — weight traffic shrinks by the packing factor, which is
    what moves the HBM roofline term;
  * unpack is a vector shift+mask on the VPU (no shared-memory bank games —
    the TPU analogue of Triton's fast unpack is simply lane-wise bit ops);
  * dequant (code - zero) * scale is fused in VMEM, then fed to the MXU with
    128-aligned tiles and an fp32 VMEM accumulator across the K grid axis.

Group boundaries must align with the K tile (bk % group_size == 0 or
group_size % bk == 0), enforced by the wrapper in ops.py.

Mosaic constraints the layout follows: uint8 codes widen to int32 before any
shift or float cast (the chip has no uint8 -> float convert), and scales and
zeros arrive as a K-resident ``(ng, bn)`` strip whose rows are sliced in the
kernel at sublane-aligned offsets (see :func:`tile_group_rows`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import PACK_FACTOR

_SUBLANES = 8     # f32 rows per vreg: dynamic row offsets must align to it


def unpack_tile(p, ppb: int, fbits: int):
    """(bk//ppb, bn) uint8 -> (bk, bn) int32 codes, matching qtensor.pack."""
    p = p.astype(jnp.int32)
    mask = (1 << fbits) - 1
    parts = [(p >> (f * fbits)) & mask for f in range(ppb)]
    w = jnp.stack(parts, axis=1)                 # (bk//ppb, ppb, bn)
    return w.reshape(p.shape[0] * ppb, p.shape[1])


def strip_rows(ng: int) -> int:
    """Row extent of the K-resident scale/zero block: ``ng`` rounded up to
    whole sublane tiles, so an aligned 8-row window never leaves it (rows
    past ``ng`` are edge padding and never selected)."""
    return -(-ng // _SUBLANES) * _SUBLANES


def tile_group_rows(ref, k, *, bk: int, group_size: int):
    """The (gpt, bn) scale/zero rows of K tile ``k`` out of a K-resident
    ``(strip_rows(ng), bn)`` strip, ``gpt = max(bk // group_size, 1)``.

    The chip loads dynamic row ranges only at multiples of 8 (or a single
    row), so 2 or 4 rows per tile come out of their aligned 8-row window
    by a static select."""
    if group_size >= bk:
        # the tile sits inside one group: one row, advancing every
        # group_size // bk K steps
        return ref[pl.ds((k * bk) // group_size, 1), :]
    gpt = bk // group_size
    row0 = k * gpt
    if gpt % _SUBLANES == 0:
        return ref[pl.ds(pl.multiple_of(row0, _SUBLANES), gpt), :]
    win = ref[pl.ds(pl.multiple_of((row0 // _SUBLANES) * _SUBLANES,
                                   _SUBLANES), _SUBLANES), :]
    off = row0 % _SUBLANES
    rows = win[:gpt]
    for o in range(gpt, _SUBLANES, gpt):
        rows = jnp.where(off == o, win[o:o + gpt], rows)
    return rows


def dequant_tile(packed, s, z, *, bits: int, dtype):
    """Unpack a (bk//ppb, bn) packed tile and dequantize it with its
    (gpt, bn) scale/zero rows into ``dtype`` (the activation dtype).

    Scales and zeros round to ``dtype`` first and the result rounds once
    more, exactly as the XLA path's ``QTensor.dequantize(x.dtype)`` does:
    with integer codes, ``code - zero`` is exact and the product of two
    ``dtype`` values is exact in f32, so both backends build bit-identical
    weights and differ only in the matmul's accumulation order."""
    ppb = PACK_FACTOR[bits]
    codes = unpack_tile(packed, ppb, 8 // ppb)                 # (bk, bn)
    bk, bn = codes.shape
    gpt = s.shape[0]
    s = s.astype(dtype).astype(jnp.float32)
    z = z.astype(dtype).astype(jnp.float32)
    cg = codes.reshape(gpt, bk // gpt, bn).astype(jnp.float32)
    w = (cg - z[:, None, :]) * s[:, None, :]
    return w.reshape(bk, bn).astype(dtype)


def _qmm_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, *,
                bits: int, nk: int, bk: int, group_size: int, k_axis: int):
    """One (bm, bn) output tile, accumulated over the K grid axis
    ``k_axis`` (2, or 3 when a squeezed expert axis leads the grid)."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = functools.partial(tile_group_rows, k=k, bk=bk,
                             group_size=group_size)
    w = dequant_tile(p_ref[...], rows(s_ref), rows(z_ref), bits=bits,
                     dtype=x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def check_group_tile(bk: int, group_size: int):
    """The group/tile contract of both kernels: one of bk, group_size
    divides the other, and a tile holding several groups holds a divisor
    or a multiple of 8 of them (``tile_group_rows``'s aligned windows)."""
    if bk % group_size and group_size % bk:
        raise ValueError(f"bk={bk} and group_size={group_size} must divide "
                         "one another")
    gpt = bk // group_size
    if gpt > 1 and gpt % _SUBLANES and _SUBLANES % gpt:
        raise ValueError(f"bk={bk} holds {gpt} groups of {group_size}; "
                         f"need a divisor or a multiple of {_SUBLANES}")


def quant_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                 zero: jax.Array, *, bits: int, group_size: int,
                 block_m: int = 256, block_n: int = 256, block_k: int = 512,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16/f32; packed: (K//ppb, N) uint8; scale/zero: (K//g, N).

    Returns (M, N) in x.dtype.  All of M, N, K must divide by the block
    sizes (the ops.py wrapper pads); block_k must be a multiple of
    group_size or vice versa.
    """
    M, K = x.shape
    ppb = PACK_FACTOR[bits]
    N = packed.shape[1]
    if packed.shape[0] != K // ppb or K % ppb:
        raise ValueError(
            f"packed rows {packed.shape[0]} inconsistent with K={K} at "
            f"{bits} bits (expected K/{ppb}={K // ppb}) — pad every K-keyed "
            "operand together (see ops.quant_matmul_op); under "
            "tensor-parallel serving these are SHARD-local shapes, so a "
            "mismatch here means the in-channel split broke the packing "
            "contract (serve_plan requires (K/ppb) % tp == 0)")
    if K % group_size or scale.shape[0] != K // group_size \
            or zero.shape[0] != K // group_size:
        raise ValueError(
            f"scale/zero rows {scale.shape[0]}/{zero.shape[0]} inconsistent "
            f"with K={K}, group_size={group_size} (expected "
            f"{max(K // group_size, 1)} whole groups) — pad every K-keyed "
            "operand together (see ops.quant_matmul_op); under "
            "tensor-parallel serving these are SHARD-local shapes — an "
            "in-channel split must take whole quant groups (serve_plan "
            "requires ng % tp == 0)")
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    check_group_tile(bk, group_size)
    nk = K // bk
    ns = strip_rows(K // group_size)

    grid = (M // bm, N // bn, nk)
    kernel = functools.partial(_qmm_kernel, bits=bits, nk=nk, bk=bk,
                               group_size=group_size, k_axis=2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // ppb, bn), lambda i, j, k: (k, j)),
            # K-resident scale/zero strips: fetched once per (i, j)
            pl.BlockSpec((ns, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((ns, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, packed, scale, zero)


def quant_matmul_experts(x: jax.Array, packed: jax.Array, scale: jax.Array,
                         zero: jax.Array, *, bits: int, group_size: int,
                         block_m: int = 256, block_n: int = 256,
                         block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Expert-batched fused dequant-matmul in ONE pallas_call.

    x: (E, M, K); packed: (E, K//ppb, N) uint8; scale/zero: (E, K//g, N).
    Returns (E, M, N) in x.dtype.  The expert dim is folded into the grid
    (leading parallel axis) instead of unrolling one kernel launch per
    expert — each expert's packed tiles are still DMA'd exactly once.
    Same divisibility contract as quant_matmul, enforced per expert.
    """
    E, M, K = x.shape
    ppb = PACK_FACTOR[bits]
    N = packed.shape[2]
    if packed.shape != (E, K // ppb, N) or K % ppb:
        raise ValueError(
            f"expert packed shape {packed.shape} inconsistent with "
            f"(E={E}, K={K}, bits={bits})")
    ng = K // group_size
    if K % group_size or scale.shape != (E, ng, N) or zero.shape != (E, ng, N):
        raise ValueError(
            f"expert scale/zero shapes {scale.shape}/{zero.shape} "
            f"inconsistent with (E={E}, K={K}, group_size={group_size})")
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    check_group_tile(bk, group_size)
    nk = K // bk
    ns = strip_rows(ng)

    # the expert dim is squeezed out of every block, so the body is the
    # single-matrix kernel with its K axis moved to program_id(3)
    kernel = functools.partial(_qmm_kernel, bits=bits, nk=nk, bk=bk,
                               group_size=group_size, k_axis=3)
    grid = (E, M // bm, N // bn, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((None, bk // ppb, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((None, ns, bn), lambda e, i, j, k: (e, 0, j)),
            pl.BlockSpec((None, ns, bn), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, packed, scale, zero)
