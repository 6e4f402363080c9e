"""Pallas TPU kernel: a decode step's cache write, in place.

Each slot's one new row goes into the stacked token leaf the decode step
carries, at ``(layer, slot, pos[slot])``, and nothing else of the leaf is
touched.  The leaf is aliased input to output, so under a donated cache
the write costs the rows it writes, not a pass over the cache.

XLA's scatter does the same on the chip as a loop of one small update a
slot: a few device ops each, thousands a step, which floods a profiler
trace.  This kernel is one program a call, with one grid step a slot:

  * the layer index and the positions ride in as scalar prefetch;
  * grid step ``b`` fetches the block that holds slot ``b``'s row and
    writes it back with the row replaced.  Where the row spans the last
    two dims of the leaf ((L, B, S, Hkv, D)) the block is that row; where
    the position axis is the second-to-last ((L, B, S, D), the latent
    cache) the block is the ``rows``-row tile that holds it;
  * a slot at ``pos >= S`` (the scheduler's inactive slots) writes its
    block back unchanged: a no-op, never a write onto the last row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _write_kernel(layer_ref, pos_ref, new_ref, leaf_ref, out_ref, *, S: int,
                  rows: int | None):
    p = pos_ref[pl.program_id(0)]
    hit = p < S
    if rows:    # the block is a tile of positions: replace the slot's row
        hit = hit & (jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                     == p % rows)
    out_ref[...] = jnp.where(hit, new_ref[...].astype(out_ref.dtype),
                             leaf_ref[...])


def _tile_rows(S: int, dtype) -> int:
    """Rows of the latent leaf's block: the chip's sublane tile for the
    dtype (8 rows of 32 bits), or the whole axis where that tile does not
    divide it."""
    rows = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return rows if S % rows == 0 else S


def cache_write(leaf: jax.Array, new: jax.Array, layer: jax.Array,
                pos: jax.Array, *, interpret: bool = False) -> jax.Array:
    """leaf (L, B, S, *tail) a stacked token leaf; new (B, *tail) each
    slot's row; layer int32 scalar; pos (B,) int32.  Returns the leaf with
    ``new[b]`` at ``(layer, b, pos[b])`` for every ``pos[b] < S``; the
    output aliases ``leaf``."""
    B, S, tail = leaf.shape[1], leaf.shape[2], leaf.shape[3:]
    if new.shape != (B,) + tail or not tail:
        raise ValueError(f"rows {new.shape} do not fit leaf {leaf.shape}")
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               pos.astype(jnp.int32))
    zeros = (0,) * len(tail)
    if len(tail) >= 2:
        kernel = functools.partial(_write_kernel, S=S, rows=None)
        new_spec = pl.BlockSpec((None,) + tail, lambda b, *_: (b,) + zeros)
        leaf_spec = pl.BlockSpec(
            (None, None, None) + tail,
            lambda b, lr, pr: (lr[0], b, jnp.minimum(pr[b], S - 1)) + zeros)
    else:
        rows = _tile_rows(S, leaf.dtype)
        kernel = functools.partial(_write_kernel, S=S, rows=rows)
        new = new[:, None]
        new_spec = pl.BlockSpec((None, 1) + tail,
                                lambda b, *_: (b, 0) + zeros)
        leaf_spec = pl.BlockSpec(
            (None, None, rows) + tail,
            lambda b, lr, pr: (lr[0], b, jnp.minimum(pr[b], S - 1) // rows)
            + zeros)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(B,),
        in_specs=[new_spec, leaf_spec], out_specs=leaf_spec)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        input_output_aliases={len(scalars) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, new, leaf)
