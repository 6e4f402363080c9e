"""Pallas TPU kernel: fused packed-weight dequantization + matmul.

This is the paper's deployment kernel (Table 8: the INT2/INT4 dequant kernel
that turns memory-bound decode into a win), adapted from its Triton/CUDA form
to the TPU memory hierarchy:

  * packed weights (uint8, ``ppb`` values per byte) are DMA'd HBM->VMEM per
    (bk x bn) tile — weight traffic shrinks by the packing factor, which is
    what moves the HBM roofline term;
  * unpack is a vector shift+mask on the VPU (no shared-memory bank games —
    the TPU analogue of Triton's fast unpack is simply lane-wise bit ops);
  * dequant (code - zero) * scale is fused in VMEM, once per (bk x bn) tile
    per call, into a scratch tile in the activation dtype; every row of the
    call's ``x`` then passes that one tile on the MXU, ``_SUB_M`` rows a
    dot, into an fp32 VMEM accumulator across the K grid axis.

The dequant is vector work of ~15 ps a weight on a v5e, several times a
tile's MXU time at a few hundred rows, so it is done once per call: the
call's M extent is one block whenever that block fits ``_VMEM_BUDGET``
(:func:`qmm_tiles`, sized from the call's static shapes), and the grid is
``(N // bn, K // bk)``.  Only a call past that row cap gets a leading M
axis of equal tiles, each of which dequantizes again.

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
_LANES = 128
# what a grid step's blocks (double-buffered) and scratch may take: a v5e
# has 128 MiB of VMEM; this holds one block of 4096 rows at 512 columns
# over any K, in bf16 or float32, with room for the compiler's own scratch
_VMEM_BUDGET = 48 * 2**20
_BLOCK_N = (512, 256, 128)
# rows of x per dot against the dequantized tile
_SUB_M = 256


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
    ``dtype`` values is exact in f32, so ``quant_matmul`` and the XLA path
    build bit-identical weights and differ only in the matmul's
    accumulation order.  The decode GEMV (``quant_gemv``) does not use
    this: it never rounds the weight, and applies scale and zero per
    group."""
    ppb = PACK_FACTOR[bits]
    codes = unpack_tile(packed, ppb, 8 // ppb)                 # (bk, bn)
    bk, bn = codes.shape
    gpt = s.shape[0]
    s = s.astype(dtype).astype(jnp.float32)
    z = z.astype(dtype).astype(jnp.float32)
    cg = codes.reshape(gpt, bk // gpt, bn).astype(jnp.float32)
    w = (cg - z[:, None, :]) * s[:, None, :]
    return w.reshape(bk, bn).astype(dtype)


def _each_rows(rows: int, fn):
    """``fn(pl.ds(r0, n))`` over ``rows`` rows in blocks of ``_SUB_M``: a
    rolled loop over the whole blocks, then the remainder (a multiple of
    the sublane tile) at a static offset."""
    full, rem = divmod(rows, _SUB_M)
    if full:
        def body(i, carry):
            fn(pl.ds(pl.multiple_of(i * _SUB_M, _SUB_M), _SUB_M))
            return carry
        jax.lax.fori_loop(0, full, body, 0)
    if rem:
        fn(pl.ds(full * _SUB_M, rem))


def _qmm_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, w_ref, *,
                bits: int, nk: int, bk: int, group_size: int, k_axis: int):
    """One (bm, bn) output block, accumulated over the K grid axis
    ``k_axis`` (the grid's last): the K tile's weights are dequantized once
    into ``w_ref`` and every row of ``x_ref`` passes them, ``_SUB_M`` rows
    a dot."""
    k = pl.program_id(k_axis)
    rows = x_ref.shape[0]

    @pl.when(k == 0)
    def _init():
        def zero(r):
            acc_ref[r, :] = jnp.zeros((r.size, acc_ref.shape[1]),
                                      jnp.float32)
        _each_rows(rows, zero)

    group_rows = functools.partial(tile_group_rows, k=k, bk=bk,
                                   group_size=group_size)
    w_ref[...] = dequant_tile(p_ref[...], group_rows(s_ref),
                              group_rows(z_ref), bits=bits,
                              dtype=x_ref.dtype)

    def accumulate(r):
        acc_ref[r, :] += jnp.dot(x_ref[r, :], w_ref[...],
                                 preferred_element_type=jnp.float32)
    _each_rows(rows, accumulate)

    @pl.when(k == nk - 1)
    def _done():
        def store(r):
            o_ref[r, :] = acc_ref[r, :].astype(o_ref.dtype)
        _each_rows(rows, store)


def check_group_tile(bk: int, group_size: int):
    """The group/tile contract of the GEMM kernels: one of bk, group_size
    divides the other, and a tile holding several groups holds a divisor
    or a multiple of 8 of them (``tile_group_rows``'s aligned windows)."""
    if bk % group_size and group_size % bk:
        raise ValueError(f"bk={bk} and group_size={group_size} must divide "
                         "one another")
    gpt = bk // group_size
    if gpt > 1 and gpt % _SUBLANES and _SUBLANES % gpt:
        raise ValueError(f"bk={bk} holds {gpt} groups of {group_size}; "
                         f"need a divisor or a multiple of {_SUBLANES}")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def row_align(itemsize: int) -> int:
    """Rows of one sublane tile of x: 8 at float32, 16 at bfloat16."""
    return _SUBLANES * 4 // itemsize


def _vmem_parts(bn: int, bk: int, *, ppb: int, group_size: int, K: int,
                itemsize: int):
    """``(fixed, per_row)``: the VMEM bytes of a grid step at ``bn``
    columns are ``fixed + bm * per_row``.  Per row: x and the output
    blocks twice (the pipeline's double buffer) and the float32
    accumulator.  Fixed: the packed codes and the K-resident scale and
    zero strips twice, the dequantized tile, the dequant's temporaries
    (int32 codes, float32 codes and product) and a row block's (a dot's
    float32 result and the accumulator rows it adds to)."""
    lanes_k, lanes_n = _round_up(bk, _LANES), _round_up(bn, _LANES)
    per_row = 2 * (lanes_k + lanes_n) * itemsize + lanes_n * 4
    codes = _round_up(bk // ppb, 4 * _SUBLANES) * lanes_n
    strips = 2 * strip_rows(K // group_size) * lanes_n * 4
    fixed = (2 * (codes + strips) + bk * lanes_n * itemsize
             + 3 * bk * lanes_n * 4 + 2 * _SUB_M * lanes_n * 4)
    return fixed, per_row


def vmem_bytes(bm: int, bn: int, bk: int, **kw) -> int:
    """VMEM a grid step of ``bm`` rows and ``bn`` columns needs
    (:func:`_vmem_parts`)."""
    fixed, per_row = _vmem_parts(bn, bk, **kw)
    return fixed + bm * per_row


def qmm_tiles(M: int, K: int, N: int, *, ppb: int, group_size: int, bk: int,
              itemsize: int, block_m=None, block_n=None):
    """``(bm, bn, Mp, Np)``: the row and column blocks of a call of ``M``
    rows over K x N, and the row and column counts to pad to.

    M pads to the sublane tile only.  Columns: ``N`` itself when it is at
    most one lane tile, else ``N`` padded to whole lane tiles and the
    widest of ``_BLOCK_N`` that divides it.  The row cap at each width is
    the most rows whose grid step fits ``_VMEM_BUDGET``, and ``block_m``
    when given and smaller.  The first width whose cap holds every row
    gives one M block, so the call dequantizes each weight tile once;
    past every cap, the widest width and equal M tiles of at most its cap.
    ``block_n`` caps the width (below one lane tile, only in interpret
    mode)."""
    align = row_align(itemsize)
    rows = _round_up(M, align)
    cap_n = block_n or _BLOCK_N[0]
    if N <= min(cap_n, _LANES):
        Np, widths = N, (N,)
    elif cap_n < _LANES:
        Np, widths = _round_up(N, cap_n), (cap_n,)
    else:
        Np = _round_up(N, _LANES)
        widths = tuple(w for w in _BLOCK_N if w <= cap_n and Np % w == 0)

    def row_cap(bn):
        fixed, per_row = _vmem_parts(bn, bk, ppb=ppb, group_size=group_size,
                                     K=K, itemsize=itemsize)
        cap = (_VMEM_BUDGET - fixed) // per_row // align * align
        if block_m is not None:
            cap = min(cap, _round_up(block_m, align))
        return max(cap, align)

    for bn in widths:
        if rows <= row_cap(bn):
            return rows, bn, rows, Np
    bn = widths[0]
    mt = -(-rows // row_cap(bn))
    bm = _round_up(-(-rows // mt), align)
    return bm, bn, mt * bm, Np


def _qmm_call(x, packed, scale, zero, *, bits: int, group_size: int,
              block_m, block_n, block_k: int, interpret: bool):
    """The pallas_call of :func:`quant_matmul` (``x`` 2-D) and of
    :func:`quant_matmul_experts` (a leading expert axis on every operand,
    squeezed out of every block, so the body is the same)."""
    lead = x.shape[:-2]
    M, K = x.shape[-2:]
    N = packed.shape[-1]
    ppb = PACK_FACTOR[bits]
    itemsize = jnp.dtype(x.dtype).itemsize
    bk = min(block_k, K)
    assert K % bk == 0, (K, bk)
    check_group_tile(bk, group_size)
    bm, bn, Mp, Np = qmm_tiles(M, K, N, ppb=ppb, group_size=group_size,
                               bk=bk, itemsize=itemsize, block_m=block_m,
                               block_n=block_n)
    assert (Mp, Np) == (M, N), ("pad M and N to the tiles first (see "
                                "ops.quant_matmul_op)", M, N, Mp, Np)
    nk = K // bk
    ns = strip_rows(K // group_size)
    mt = M // bm
    # a leading M axis only for a call past the row cap
    grid = lead + ((mt,) if mt > 1 else ()) + (N // bn, nk)

    def spec(block, index):
        def imap(*g):
            e, (j, k) = g[:len(lead)], g[-2:]
            i = g[len(lead)] if mt > 1 else 0
            return e + index(i, j, k)
        return pl.BlockSpec((None,) * len(lead) + block, imap)

    kernel = functools.partial(_qmm_kernel, bits=bits, nk=nk, bk=bk,
                               group_size=group_size, k_axis=len(grid) - 1)
    # a quarter over the count, asked for only past the 16 MiB default
    ask = vmem_bytes(bm, bn, bk, ppb=ppb, group_size=group_size, K=K,
                     itemsize=itemsize) * 5 // 4
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec((bm, bk), lambda i, j, k: (i, k)),
            spec((bk // ppb, bn), lambda i, j, k: (k, j)),
            # K-resident scale/zero strips: fetched once per column block
            spec((ns, bn), lambda i, j, k: (0, j)),
            spec((ns, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=spec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(lead + (M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bk, bn), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",),
            vmem_limit_bytes=ask if ask > 16 * 2**20 else None),
        interpret=interpret,
    )(x, packed, scale, zero)


def quant_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                 zero: jax.Array, *, bits: int, group_size: int,
                 block_m=None, block_n=None, block_k: int = 512,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16/f32; packed: (K//ppb, N) uint8; scale/zero: (K//g, N).

    Returns (M, N) in x.dtype.  M and N must already be the padded counts
    :func:`qmm_tiles` gives (the ops.py wrapper pads), and K must divide by
    block_k, a multiple of group_size or a divisor of it.  ``block_m`` and
    ``block_n`` cap the row and column blocks below what the shapes and
    the VMEM budget give (tests force several M tiles with them).
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
    return _qmm_call(x, packed, scale, zero, bits=bits,
                     group_size=group_size, block_m=block_m,
                     block_n=block_n, block_k=block_k, interpret=interpret)


def quant_matmul_experts(x: jax.Array, packed: jax.Array, scale: jax.Array,
                         zero: jax.Array, *, bits: int, group_size: int,
                         block_m=None, block_n=None, block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Expert-batched fused dequant-matmul in ONE pallas_call.

    x: (E, M, K); packed: (E, K//ppb, N) uint8; scale/zero: (E, K//g, N).
    Returns (E, M, N) in x.dtype.  The expert dim is folded into the grid
    (leading parallel axis) instead of unrolling one kernel launch per
    expert — each expert's packed tiles are still DMA'd exactly once.
    Same tiles and padding contract as quant_matmul, per expert.
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
    return _qmm_call(x, packed, scale, zero, bits=bits,
                     group_size=group_size, block_m=block_m,
                     block_n=block_n, block_k=block_k, interpret=interpret)
