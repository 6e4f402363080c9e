"""Pallas TPU kernel: W4A4/W4A8-style integer matmul with per-token and
per-channel scales (paper Sec. 4.2, weight-activation quantization).

The activation is quantized per token *outside* the kernel (a cheap VPU
row-reduce, fused by XLA into the producer); the kernel consumes int8 x and
int8 w tiles, accumulates in int32 on the MXU, and applies
row_scale x col_scale on the fp32 epilogue — the TPU analogue of the CUDA
int8 tensor-core pipeline."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _i8mm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32)
        out = out * sx_ref[...][:, 0][:, None] * sw_ref[...][0][None, :]
        o_ref[...] = out.astype(o_ref.dtype)


def int8_matmul(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                w_scale: jax.Array, *, out_dtype=jnp.bfloat16,
                block_m: int = 256, block_n: int = 256, block_k: int = 512,
                interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M, 1) f32 per token;
    w_scale: (1, N) f32 per channel.  Returns (M, N) out_dtype."""
    M, K = x_q.shape
    N = w_q.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_i8mm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale)
