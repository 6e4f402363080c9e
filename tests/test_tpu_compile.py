"""Ahead-of-time compiles of the serve-path Pallas kernels for a described
TPU v5e chip, at tinyllama-1.1b widths (K=2048, N=5632; 4 KV heads of 64,
8 query heads per KV head) and, for the expert GEMM, qwen3-moe-30b-a3b's
(K=2048, N=768 per expert).

Interpret mode, which the rest of the suite runs the kernels in, accepts
layouts the chip's compiler refuses (uint8 -> float casts, scale blocks
with 2 or 4 rows, rank-1 SMEM blocks, a k/v block with one head in its
second-to-last dim).  These tests run the chip's compiler on the kernels
with ``interpret=False`` — no chip is needed, and nothing executes.

The topology is described inside a fixture: only the worker that runs this
file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qtensor import PACK_FACTOR
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.quant_gemv import quant_gemv
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_experts

K, N = 2048, 5632
N_EXPERT = 768
B, HKV, G, D = 4, 4, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for a described chip writes persistent-cache entries
    # that cannot be read back without that chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _packed_operands(one_chip, bits, group, M, *, n=N, lead=()):
    ppb = PACK_FACTOR[bits]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                                 sharding=one_chip)
    return (sds((M, K), jnp.bfloat16), sds((K // ppb, n), jnp.uint8),
            sds((K // group, n), jnp.float32),
            sds((K // group, n), jnp.float32))


@pytest.mark.parametrize("M", [1, 8])
@pytest.mark.parametrize("bits", [2, 4])
def test_quant_gemv_compiles(one_chip, bits, M):
    _compile(lambda x, p, s, z: quant_gemv(x, p, s, z, bits=bits,
                                           group_size=128),
             *_packed_operands(one_chip, bits, 128, M))


# block_k picks the scale-row path of ``tile_group_rows``: 16 groups per
# tile (an aligned 8-row multiple), 4 or 2 (a select out of the aligned
# 8-row window), or a tile inside one group (one row)
@pytest.mark.parametrize("bits,group,block_k", [
    (2, 32, 512), (2, 128, 512), (2, 128, 256), (2, 128, 128),
    (3, 128, 512), (4, 128, 512),
], ids=["w2g32-16gpt", "w2g128-4gpt", "w2g128-2gpt", "w2g128-1row",
        "w3g128", "w4g128"])
def test_quant_matmul_compiles(one_chip, bits, group, block_k):
    _compile(lambda x, p, s, z: quant_matmul(x, p, s, z, bits=bits,
                                             group_size=group,
                                             block_k=block_k),
             *_packed_operands(one_chip, bits, group, 256))


def test_quant_matmul_experts_compiles(one_chip):
    _compile(lambda x, p, s, z: quant_matmul_experts(x, p, s, z, bits=2,
                                                     group_size=128),
             *_packed_operands(one_chip, 2, 128, 128, n=N_EXPERT, lead=(8,)))


def _attn_operands(one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (sds((B, HKV, G, D), jnp.bfloat16), sds((B,), jnp.int32),
            sds((B,), jnp.int32), sds((B,), jnp.bool_), sds)


def test_decode_attention_compiles(one_chip):
    q, kv_len, q_pos, active, sds = _attn_operands(one_chip)
    kv = sds((B, 1024, HKV, D), jnp.bfloat16)
    _compile(lambda q, k, v, n, p, a: decode_attention(
        q, k, v, kv_len=n, q_pos=p, active=a), q, kv, kv, kv_len, q_pos,
        active)


def test_paged_decode_attention_compiles(one_chip):
    q, kv_len, q_pos, active, sds = _attn_operands(one_chip)
    psz, W = 16, 1024 // 16
    pool = sds((B * W, psz, HKV, D), jnp.bfloat16)
    ptab = sds((B, W), jnp.int32)
    _compile(lambda q, k, v, t, n, p, a: paged_decode_attention(
        q, k, v, t, kv_len=n, q_pos=p, active=a), q, pool, pool, ptab,
        kv_len, q_pos, active)


def _custom_call_names(text):
    return {line.split(" = ", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in text.splitlines()
            if "custom_call_target=\"tpu_custom_call\"" in line}


def test_decode_attention_keeps_its_name_inside_a_step(one_chip,
                                                       monkeypatch):
    """Inside a larger jitted program the attention kernels keep their
    wrappers' names, which the benchmark reads from the device trace."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    q, kv_len, q_pos, active, sds = _attn_operands(one_chip)
    kv = sds((B, 1024, HKV, D), jnp.bfloat16)
    psz, W = 16, 1024 // 16
    pool = sds((B * W, psz, HKV, D), jnp.bfloat16)
    ptab = sds((B, W), jnp.int32)

    def step(q, k, v, pool, t, n, p, a):
        dense = ops.decode_attention_op(q, k, v, kv_len=n, q_pos=p, active=a,
                                        scale=D ** -0.5, chunk=1 << 30)
        paged = ops.paged_decode_attention_op(q, pool, pool, t, kv_len=n,
                                              q_pos=p, active=a,
                                              scale=D ** -0.5)
        return (dense * 2 + paged).sum()

    text = jax.jit(step).lower(q, kv, kv, pool, ptab, kv_len, q_pos,
                               active).compile().as_text()
    assert _custom_call_names(text) == {"decode_attention_op",
                                        "paged_decode_attention_op"}
