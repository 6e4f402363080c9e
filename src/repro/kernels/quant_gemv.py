"""Pallas TPU kernel: decode-shaped packed GEMV.

``quant_matmul`` is prefill-shaped: it dequantizes each weight tile once
per call and passes every prompt row past it.  Decode has 1..64 rows (the
live slots), so a dequant per weight would be shared by a handful of
products, and its vector work, not the HBM stream or the MXU, would set
the pace.  This kernel takes the affine dequant off the per-weight path:

  * **group-factored affine.**  With ``s``, ``z`` rounded to the activation
    dtype, as ``QTensor.dequantize`` rounds them, and ``X_g`` the sum of a
    group's activations,

        sum_k x_k (q_k - z_g) s_g = sum_g s_g (x_g . q_g  -  z_g X_g)

    so the inner loop only unpacks codes and sends each group's codes to
    the MXU against the group's columns of ``x``.  Scale and zero touch
    the ``(M, bn)`` partial once per group, not each of the group's
    weights.  Unlike ``quant_matmul`` the weight ``(q - z) s`` is never
    rounded to the activation dtype: the result is nearer the float32
    product.
  * **plane-order unpack.**  A byte of ``qtensor.pack`` holds ``ppb``
    codes of consecutive K rows.  Each bit field of a unit's packed rows
    is read as its own plane, masked in place (``byte & (mask << f *
    fbits)``, worth ``code * 2**(f * fbits)``, exact in bf16) and the
    planes are stacked along sublanes at aligned offsets: no shift, and
    no interleave back into K order.  ``x``'s columns go to the same
    order instead, each scaled by ``2**-(f * fbits)``: the first grid step
    multiplies ``x`` by a 0/1-times-power-of-two matrix on the MXU (exact)
    into a VMEM scratch, with the group sums ``X_g`` beside it.  Callers
    pass ``x`` in K order, and the call adds no XLA op.  A unit is a
    whole group, or, for groups of more than ``_UNIT_MAX`` rows
    (per-channel weights), a divisor of the group whose partials add up
    before the group's scale is applied.
  * **shape-derived tiles, resident x.**  The grid runs over N only: each
    step DMAs the packed codes of all of K for ``bn`` columns, with their
    scale and zero rows, and loops over the groups inside the kernel,
    ``_UNROLL`` groups a trip.  ``x`` is one block whose index never
    changes, so it is fetched once per call.  ``bn`` comes from the
    call's static shapes and ``_VMEM_BUDGET`` (:func:`gemv_block_n`).

The packed HBM format is ``qtensor.pack``'s, unchanged: uint8 codes
``(K // ppb, N)`` and float32 scale/zero ``(K // group_size, N)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import PACK_FACTOR

# what a grid step's blocks (double-buffered) and scratch may take: below
# v5e's 16 MiB default scoped VMEM limit, with room for the loop's spills
_VMEM_BUDGET = 12 * 2**20
# most K rows unpacked and sent to the MXU at a time
_UNIT_MAX = 256
# groups (or units) per trip of the kernel's loops: Mosaic lowers a loop
# either rolled or whole, so the body holds this many, and the scheduler
# overlaps one group's unpack with the last one's MXU work and epilogue
_UNROLL = 8
_BLOCK_N = (1024, 512, 256, 128)
_LANES = 128


def plane_unit(group_size: int, ppb: int) -> int:
    """K rows the kernel unpacks at a time: the whole group when it has at
    most ``_UNIT_MAX`` rows, else the largest divisor of the group up to
    ``_UNIT_MAX`` that holds whole packed rows."""
    if group_size <= _UNIT_MAX:
        return group_size
    return max(d for d in range(ppb, _UNIT_MAX + 1, ppb)
               if group_size % d == 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(M, K, bn, ppb, group_size, itemsize) -> int:
    """VMEM a grid step needs at ``bn`` columns: every block twice (the
    pipeline's double buffer) and the scratch (``x`` in plane order and
    its group sums)."""
    unit = plane_unit(group_size, ppb)
    rows = _round_up(M, 16)
    x = rows * _round_up(K, _LANES) * itemsize
    codes = _round_up(K // ppb, 32) * bn
    scales = 2 * _round_up(K // group_size, 8) * bn * 4
    out = rows * bn * itemsize
    scratch = (K // unit * rows * _round_up(unit, _LANES) * itemsize
               + K // group_size * rows * _LANES * 4)
    return 2 * (x + codes + scales + out) + scratch


def gemv_block_n(M: int, K: int, N: int, ppb: int, group_size: int,
                 itemsize: int) -> int:
    """Columns a grid step covers: the widest of ``_BLOCK_N`` that divides
    ``N`` and fits ``_VMEM_BUDGET``.  ``N`` is below 128 (one full block)
    or a multiple of 128 (the wrapper pads).  Wide steps beat more of
    them: on a v5e one 1024-column step over K=4096 ran faster than two of
    512, its exposed first DMA included."""
    if N < _LANES:
        return N
    for bn in _BLOCK_N:
        if N % bn == 0 and _vmem_bytes(M, K, bn, ppb, group_size,
                                       itemsize) <= _VMEM_BUDGET:
            return bn
    return _LANES


def _unrolled(n: int, body, carry):
    """``fori_loop(0, n, body, carry)`` with ``_UNROLL`` iterations a trip
    and the remainder after it; straight-line code when ``n`` is at most
    one trip."""
    if n <= _UNROLL:
        for i in range(n):
            carry = body(i, carry)
        return carry

    def trip(i, c):
        for t in range(_UNROLL):
            c = body(i * _UNROLL + t, c)
        return c
    carry = jax.lax.fori_loop(0, n // _UNROLL, trip, carry)
    for i in range(n - n % _UNROLL, n):
        carry = body(i, carry)
    return carry


def _each_cols(x_ref, size: int, fn):
    """``fn(i, x_ref[:, i * size:(i + 1) * size])`` for every ``i``: in a
    rolled loop (``_unrolled``) where the offsets are whole lane tiles (the
    chip slices lanes dynamically only there), else at static offsets."""
    n = x_ref.shape[1] // size
    if size % _LANES == 0:
        def one(i, carry):
            fn(i, x_ref[:, pl.ds(pl.multiple_of(i * size, _LANES), size)])
            return carry
        _unrolled(n, one, 0)
    else:
        for i in range(n):
            fn(i, x_ref[:, i * size:(i + 1) * size])


def _fill_planes(x_ref, xp_ref, xs_ref, *, ppb: int, unit: int, upg: int):
    """x_ref (M, K) in K order to the plane-order scratch xp_ref (K // unit,
    M, unit), field ``f``'s columns times ``2**-(f * fbits)``, and each
    group's sum of ``x`` to xs_ref (K // group_size, M, 1) float32."""
    fbits = 8 // ppb
    rows = unit // ppb
    dt = x_ref.dtype
    f32 = jnp.float32
    # x's columns to plane order by a matrix on the MXU.  Its one nonzero a
    # column is a power of two, so the product is exact in any dtype, and
    # it takes the place of the shift that would bring each field of a
    # byte down to bit 0
    k = jax.lax.broadcasted_iota(jnp.int32, (unit, unit), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (unit, unit), 1)
    weight = jnp.exp2(-((k % ppb) * fbits).astype(f32))
    perm = jnp.where(c == (k % ppb) * rows + k // ppb, weight,
                     0.0).astype(dt)
    exact = jax.lax.Precision.HIGHEST if dt == f32 else None

    def to_planes(u, xu):
        xp_ref[u] = jnp.dot(xu, perm, precision=exact,
                            preferred_element_type=f32).astype(dt)
        if upg == 1:
            group_sum(u, xu)

    def group_sum(g, xg):
        xs_ref[g] = jnp.sum(xg.astype(f32), axis=1, keepdims=True)
    _each_cols(x_ref, unit, to_planes)
    if upg > 1:
        _each_cols(x_ref, unit * upg, group_sum)


def _group_dot(p_ref, s_ref, z_ref, xp_ref, xs_ref, shape, *, ppb: int,
               unit: int, upg: int):
    """The ``shape`` (M, bn) product over all of K, float32: p_ref (K // ppb,
    bn) uint8 codes, s_ref / z_ref (K // group_size, bn) float32, x from
    the plane-order scratch :func:`_fill_planes` filled."""
    fbits = 8 // ppb
    mask = (1 << fbits) - 1
    rows = unit // ppb
    dt = xp_ref.dtype
    f32 = jnp.float32

    def unit_dot(u, part):
        """Unit ``u``'s codes against its x columns, added to ``part``
        (``None`` for the group's first unit)."""
        r0 = pl.multiple_of(u * rows, rows)
        # uint8 widens to int32 before any bit op (Mosaic has no uint8
        # vector arithmetic and no uint8 -> float convert); each field is
        # masked in place, worth code << (f * fbits), exact in bf16
        p = p_ref[pl.ds(r0, rows), :].astype(jnp.int32)
        q = p if ppb == 1 else jnp.concatenate(
            [p & (mask << (f * fbits)) for f in range(ppb)], axis=0)
        d = jnp.dot(xp_ref[u], q.astype(f32).astype(dt),
                    preferred_element_type=f32)
        return d if part is None else part + d

    def group(g, acc):
        part = unit_dot(g * upg, None)
        if upg > 1:
            part = _unrolled(upg - 1,
                             lambda i, c: unit_dot(g * upg + 1 + i, c), part)
        xsum = xs_ref[g]
        # scale and zero round to the activation dtype first, exactly as
        # QTensor.dequantize rounds them
        s = s_ref[pl.ds(g, 1), :].astype(dt).astype(f32)
        z = z_ref[pl.ds(g, 1), :].astype(dt).astype(f32)
        return acc + s * (part - z * xsum)

    return _unrolled(s_ref.shape[0], group, jnp.zeros(shape, f32))


def _gemv_kernel(x_ref, p_ref, s_ref, z_ref, o_ref, xp_ref, xs_ref, *,
                 ppb: int, unit: int, upg: int):
    """One ``(M, bn)`` output block over all of K.

    x_ref: (M, K) in K order; p_ref: (K // ppb, bn) uint8; s_ref / z_ref:
    (K // group_size, bn) float32.  Scratch, filled by the first grid step
    for the rest: xp_ref (K // unit, M, unit), ``x`` in plane order with
    field ``f``'s columns times ``2**-(f * fbits)``; xs_ref (K //
    group_size, M, 1) float32, each group's sum of ``x``."""
    kw = dict(ppb=ppb, unit=unit, upg=upg)

    @pl.when(pl.program_id(0) == 0)
    def _plane_order():
        # once per call: x stays the same for every column block
        _fill_planes(x_ref, xp_ref, xs_ref, **kw)

    acc = _group_dot(p_ref, s_ref, z_ref, xp_ref, xs_ref, o_ref.shape, **kw)
    o_ref[...] = acc.astype(o_ref.dtype)


def quant_gemv(x: jax.Array, packed: jax.Array, scale: jax.Array,
               zero: jax.Array, *, bits: int, group_size: int,
               interpret: bool = False) -> jax.Array:
    """x: (M, K) in K order, M = live decode slots (never padded); packed:
    (K//ppb, N) uint8; scale/zero: (K//g, N) f32.

    Returns (M, N) in x.dtype.  N is below 128 or a multiple of 128 (the
    ops.py wrapper pads); K needs no padding."""
    M, K = x.shape
    ppb = PACK_FACTOR[bits]
    N = packed.shape[1]
    if packed.shape[0] != K // ppb or K % ppb:
        raise ValueError(
            f"packed rows {packed.shape[0]} inconsistent with K={K} at "
            f"{bits} bits (expected K/{ppb}={K // ppb}); under "
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
    if group_size % ppb:
        raise ValueError(f"group_size={group_size} splits a packed byte "
                         f"({ppb} codes at {bits} bits)")
    unit = plane_unit(group_size, ppb)
    itemsize = jnp.dtype(x.dtype).itemsize
    bn = gemv_block_n(M, K, N, ppb, group_size, itemsize)
    assert N % bn == 0, (N, bn)
    need = _vmem_bytes(M, K, bn, ppb, group_size, itemsize)
    ng = K // group_size

    kernel = functools.partial(_gemv_kernel, ppb=ppb, unit=unit,
                               upg=group_size // unit)
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            # one block with a fixed index: fetched once, kept across N
            pl.BlockSpec((M, K), lambda j: (0, 0)),
            pl.BlockSpec((K // ppb, bn), lambda j: (0, j)),
            pl.BlockSpec((ng, bn), lambda j: (0, j)),
            pl.BlockSpec((ng, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((K // unit, M, unit), x.dtype),
                        pltpu.VMEM((ng, M, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the first step fills the plane-order scratch for the rest
            dimension_semantics=("arbitrary",),
            # only a K too large for the budget at 128 columns asks for more
            vmem_limit_bytes=max(need + need // 4, 16 * 2**20)
            if need > _VMEM_BUDGET else None),
        interpret=interpret,
    )(x, packed, scale, zero)
