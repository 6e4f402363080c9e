"""Pallas TPU kernel: decode-shaped fused dequant-GEMV.

``quant_matmul`` is prefill-shaped: 256-row M tiles and an (M, N, K) grid
amortize the dequant over many activation rows.  Decode inverts the regime —
M is the slot count (1..~24) and the matmul is purely memory-bound on the
packed weight stream, which is exactly where the paper's Table 8 claim lives:
the ``ppb`` packing factor shrinks HBM weight traffic, so the kernel must
read each packed byte once and never pad M.

Differences from the prefill kernel:

  * grid is (N, K) only — the whole activation block (true M, no row
    padding) rides along every program instance instead of being tiled;
  * scales/zeros are K-resident: the full (K//g, bn) column strip is DMA'd
    once per N tile and the per-K-tile rows are sliced *inside* the kernel,
    so the grid never re-fetches them as k advances;
  * for very small M the MXU is skipped entirely — a broadcast
    multiply-reduce on the VPU avoids padding 1..4 rows up to the MXU's
    8-row granularity.

Same group/tile contract and tile helpers as quant_matmul
(``check_group_tile``), enforced by the wrapper in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import PACK_FACTOR
from repro.kernels.quant_matmul import (check_group_tile, dequant_tile,
                                        strip_rows, tile_group_rows)

# below this many activation rows the MXU tile padding costs more than the
# VPU broadcast-multiply-reduce; decode with a handful of busy slots lands here
_VPU_MAX_ROWS = 4


def _gemv_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, *,
                 bits: int, nk: int, bk: int, group_size: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # K-resident scales: this K tile's group rows come out of the full strip
    rows = functools.partial(tile_group_rows, k=k, bk=bk,
                             group_size=group_size)
    w = dequant_tile(p_ref[...], rows(s_ref), rows(z_ref), bits=bits,
                     dtype=x_ref.dtype)
    x = x_ref[...]
    if x.shape[0] <= _VPU_MAX_ROWS:
        # bf16 x bf16 products are exact in f32, so this differs from the
        # MXU dot only in f32 reduction order
        acc_ref[...] += jnp.sum(x.astype(jnp.float32)[:, :, None]
                                * w.astype(jnp.float32)[None, :, :], axis=1)
    else:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_gemv(x: jax.Array, packed: jax.Array, scale: jax.Array,
               zero: jax.Array, *, bits: int, group_size: int,
               block_n: int = 128, block_k: int = 256,
               interpret: bool = False) -> jax.Array:
    """x: (M, K) with M = live decode slots (kept at TRUE size, never
    padded); packed: (K//ppb, N) uint8; scale/zero: (K//g, N) f32.

    Returns (M, N) in x.dtype.  N and K must divide by the block sizes
    (the ops.py wrapper pads); block_k must be a multiple of group_size or
    vice versa.
    """
    M, K = x.shape
    ppb = PACK_FACTOR[bits]
    N = packed.shape[1]
    if packed.shape[0] != K // ppb or K % ppb:
        raise ValueError(
            f"packed rows {packed.shape[0]} inconsistent with K={K} at "
            f"{bits} bits (expected K/{ppb}={K // ppb}) — pad every K-keyed "
            "operand together (see ops.quant_gemv_op); under "
            "tensor-parallel serving these are SHARD-local shapes, so a "
            "mismatch here means the in-channel split broke the packing "
            "contract (serve_plan requires (K/ppb) % tp == 0)")
    if K % group_size or scale.shape[0] != K // group_size \
            or zero.shape[0] != K // group_size:
        raise ValueError(
            f"scale/zero rows {scale.shape[0]}/{zero.shape[0]} inconsistent "
            f"with K={K}, group_size={group_size}; under tensor-parallel "
            "serving these are SHARD-local shapes — an in-channel split "
            "must take whole quant groups (serve_plan requires "
            "ng % tp == 0)")
    bn, bk = min(block_n, N), min(block_k, K)
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    check_group_tile(bk, group_size)
    nk = K // bk
    ns = strip_rows(K // group_size)

    kernel = functools.partial(_gemv_kernel, bits=bits, nk=nk, bk=bk,
                               group_size=group_size)
    return pl.pallas_call(
        kernel,
        grid=(N // bn, nk),
        in_specs=[
            pl.BlockSpec((M, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk // ppb, bn), lambda j, k: (k, j)),
            # full K strip of scales per N tile, sliced in-kernel
            pl.BlockSpec((ns, bn), lambda j, k: (0, j)),
            pl.BlockSpec((ns, bn), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((M, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, packed, scale, zero)
