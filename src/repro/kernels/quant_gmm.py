"""Pallas TPU kernel: dropless grouped matmul over packed expert weights.

An expert layer routes each token to ``top_k`` of ``E`` experts.  The
caller sorts the (token, choice) rows by expert and pads each expert's
block to whole tiles of ``tm`` rows (``models.mla_moe.group_layout``), so
every row tile belongs to one expert.  This kernel multiplies each tile by
its expert's packed weight:

  * **scalar prefetch.**  ``tile_expert`` (one expert id per tile) and
    ``n_tiles`` (how many tiles are real) ride in as scalar prefetch.  The
    weight's index map reads the tile's expert, so consecutive tiles of
    one expert keep their weight block (Pallas skips the copy of an
    unchanged block index), and an expert with no rows is never read.
  * **a fixed grid.**  The grid is ``(column blocks, tiles)``, tiles
    innermost, sized for the most tiles the rows can need.  Tiles past
    ``n_tiles`` repeat the last real tile's block indices, so they fetch
    nothing, and skip their compute.
  * **the GEMV's body.**  Each tile runs ``quant_gemv``'s inner loop: the
    tile's rows to plane order with their group sums, then per group the
    codes' fields masked in place against them on the MXU, scale and zero
    applied once per group to the partial product.  One body serves a
    decode step (a few rows an expert) and a prefill (about a hundred).

The packed HBM format is ``qtensor.pack``'s, expert-stacked: uint8 codes
``(E, K // ppb, N)`` and float32 scale/zero ``(E, K // group_size, N)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import PACK_FACTOR
from repro.kernels.quant_gemv import (_BLOCK_N, _LANES, _VMEM_BUDGET,
                                      _fill_planes, _group_dot, _vmem_bytes,
                                      plane_unit)


def gmm_block_n(tm: int, K: int, N: int, ppb: int, group_size: int,
                itemsize: int) -> int:
    """Columns a grid step covers: all of ``N`` when its blocks fit
    ``_VMEM_BUDGET`` (expert widths such as 1408 have no wide power-of-two
    divisor, and every column block re-reads the tile's rows), else the
    widest of ``_BLOCK_N`` that divides ``N`` and fits."""
    for bn in (N,) + _BLOCK_N:
        if N % bn == 0 and (bn == N or bn % _LANES == 0) and _vmem_bytes(
                tm, K, bn, ppb, group_size, itemsize) <= _VMEM_BUDGET:
            return bn
    return _LANES


def _gmm_kernel(te_ref, n_ref, x_ref, p_ref, s_ref, z_ref, o_ref, xp_ref,
                xs_ref, *, ppb: int, unit: int, upg: int):
    """Tile ``t`` (grid axis 1) of rows times its expert's ``bn`` columns:
    x_ref (tm, K), p_ref (K // ppb, bn), s_ref / z_ref (K // g, bn)."""
    kw = dict(ppb=ppb, unit=unit, upg=upg)

    @pl.when(pl.program_id(1) < n_ref[0])
    def _tile():
        _fill_planes(x_ref, xp_ref, xs_ref, **kw)
        acc = _group_dot(p_ref, s_ref, z_ref, xp_ref, xs_ref, o_ref.shape,
                         **kw)
        o_ref[...] = acc.astype(o_ref.dtype)


def quant_gmm(x: jax.Array, packed: jax.Array, scale: jax.Array,
              zero: jax.Array, tile_expert: jax.Array, n_tiles: jax.Array, *,
              bits: int, group_size: int, row_tile: int,
              interpret: bool = False) -> jax.Array:
    """x: (T * tm, K) rows sorted by expert, tile ``t`` being rows ``[t tm,
    (t + 1) tm)`` of expert ``tile_expert[t]``; packed (E, K // ppb, N)
    uint8; scale/zero (E, K // g, N) float32; tile_expert (T,) int32;
    n_tiles (1,) int32.

    Returns (T * tm, N) in x.dtype.  Rows of tiles past ``n_tiles`` are
    unspecified.  N is below 128 or a multiple of 128 (the ops.py wrapper
    pads)."""
    Mp, K = x.shape
    E, kp, N = packed.shape
    ppb = PACK_FACTOR[bits]
    tm = row_tile
    if Mp % tm:
        raise ValueError(f"{Mp} rows are not whole tiles of {tm}")
    T = Mp // tm
    if kp != K // ppb or K % ppb:
        raise ValueError(f"packed rows {kp} inconsistent with K={K} at "
                         f"{bits} bits (expected K/{ppb}={K // ppb})")
    ng = K // group_size
    if K % group_size or scale.shape != (E, ng, N) \
            or zero.shape != (E, ng, N):
        raise ValueError(f"scale/zero {scale.shape}/{zero.shape} are not "
                         f"(E={E}, K/group_size={ng}, N={N})")
    if group_size % ppb:
        raise ValueError(f"group_size={group_size} splits a packed byte "
                         f"({ppb} codes at {bits} bits)")
    if tile_expert.shape != (T,):
        raise ValueError(f"tile_expert {tile_expert.shape} is not ({T},)")
    unit = plane_unit(group_size, ppb)
    itemsize = jnp.dtype(x.dtype).itemsize
    bn = gmm_block_n(tm, K, N, ppb, group_size, itemsize)
    need = _vmem_bytes(tm, K, bn, ppb, group_size, itemsize)

    def rows(j, t, te, n):
        # past the real tiles: the last real tile's block, fetched already
        return (jnp.minimum(t, jnp.maximum(n[0] - 1, 0)), 0)

    def weight(j, t, te, n):
        return (te[t], 0, j)

    def out(j, t, te, n):
        return (rows(j, t, te, n)[0], j)

    kernel = functools.partial(_gmm_kernel, ppb=ppb, unit=unit,
                               upg=group_size // unit)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // bn, T),
        in_specs=[
            pl.BlockSpec((tm, K), rows),
            pl.BlockSpec((None, K // ppb, bn), weight),
            pl.BlockSpec((None, ng, bn), weight),
            pl.BlockSpec((None, ng, bn), weight),
        ],
        out_specs=pl.BlockSpec((tm, bn), out),
        scratch_shapes=[pltpu.VMEM((K // unit, tm, unit), x.dtype),
                        pltpu.VMEM((ng, tm, 1), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # padding tiles revisit the last real tile's output block
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need + need // 4, 16 * 2**20)
            if need > _VMEM_BUDGET else None),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32), x, packed,
      scale, zero)
