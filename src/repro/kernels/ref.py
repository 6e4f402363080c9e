"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qtensor import PACK_FACTOR, QTensor


def quant_matmul_ref(x, packed, scale, zero, *, bits: int, group_size: int):
    """The XLA serve path: ``QTensor.dequantize(x.dtype)``, then one
    matmul."""
    shape = (packed.shape[0] * PACK_FACTOR[bits], packed.shape[1])
    w = QTensor(packed, scale, zero, bits, group_size, shape)
    return (x @ w.dequantize(x.dtype)).astype(x.dtype)


def int8_matmul_ref(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.bfloat16):
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def soft_round_ref(base, nu, hard, v, scale, zero, *, qmax: int,
                   dst: bool = True):
    alpha = jnp.where(hard == 0, jax.nn.sigmoid(nu),
                      (hard > 0).astype(jnp.float32))
    z = zero[:, None, :]
    q = jnp.clip(base + z + alpha, 0.0, float(qmax))
    s = scale[:, None, :]
    if dst:
        s = s * (2.0 * jax.nn.sigmoid(v))[:, None, :]
    return (q - z) * s


def quantize_per_token_ref(x, bits: int = 8):
    """Symmetric per-token activation quantization -> (int8 codes, scales)."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale
