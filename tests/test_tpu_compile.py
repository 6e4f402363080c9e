"""Ahead-of-time compiles of the serve-path Pallas kernels for a described
TPU v5e chip, at tinyllama-1.1b widths (K=2048, N=5632; 4 KV heads of 64,
8 query heads per KV head), for the expert GEMM at qwen3-moe-30b-a3b's
(K=2048, N=768 per expert), and for the grouped expert matmul and the
latent decode attention at Moonlight-16B-A3B's (64 experts of 2048 x 1408;
a 576-wide latent read by 16 heads over 64 slots of 1472), and for the
decode step's in-place cache write at Mistral-7B's and Moonlight's caches,
and for the prefill GEMM at a whole prompt's rows (one M block a call).

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
from repro.kernels.cache_write import cache_write
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.quant_gemv import quant_gemv
from repro.kernels.quant_gmm import quant_gmm
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


def _packed_operands(one_chip, bits, group, M, *, k=K, n=N, lead=()):
    ppb = PACK_FACTOR[bits]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                                 sharding=one_chip)
    return (sds((M, k), jnp.bfloat16), sds((k // ppb, n), jnp.uint8),
            sds((k // group, n), jnp.float32),
            sds((k // group, n), jnp.float32))


# tinyllama's widths, then Mistral-7B's four GEMV shapes (q/o, k/v,
# gate/up, down) at 16 and 32 slots: the kernel's tiles come from the
# shapes, so a layout the chip's compiler refuses or a VMEM overrun at
# any of them shows here
_MISTRAL = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


@pytest.mark.parametrize("bits,M,k,n", [
    pytest.param(2, 1, K, N, id="2-1"), pytest.param(2, 8, K, N, id="2-8"),
    pytest.param(4, 1, K, N, id="4-1"), pytest.param(4, 8, K, N, id="4-8"),
] + [pytest.param(2, M, k, n, id=f"mistral-w2-{k}x{n}-m{M}")
     for k, n in _MISTRAL for M in (16, 32)]
  + [pytest.param(b, M, 4096, 14336, id=f"mistral-w{b}-4096x14336-m{M}")
     for b in (3, 4) for M in (16, 32)]
  # a K too deep for the VMEM budget even at 128 columns: the kernel asks
  # the compiler for the VMEM it needs
  + [pytest.param(4, 32, 65536, 1024, id="deep-k-w4-65536x1024-m32")])
def test_quant_gemv_compiles(one_chip, bits, M, k, n):
    _compile(lambda x, p, s, z: quant_gemv(x, p, s, z, bits=bits,
                                           group_size=128),
             *_packed_operands(one_chip, bits, 128, M, k=k, n=n))


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


# a B=1 prefill's whole prompt as one M block (W2g128): Mistral-7B's
# gate/up and down at the long-prompt cell's 3072 rows, the prefill at
# its 4096 sliding window, and Moonlight's kv_a (N 576, padded to 640 by
# the wrapper) at 1280 rows; a VMEM overrun shows here, not on the chip
@pytest.mark.parametrize("M,k,n", [
    (3072, 4096, 14336), (3072, 14336, 4096), (4096, 14336, 4096),
    (1280, 2048, 640),
], ids=["mistral-gate-m3072", "mistral-down-m3072", "mistral-down-m4096",
        "moonlight-kv_a-m1280"])
def test_quant_matmul_one_block_compiles(one_chip, M, k, n):
    _compile(lambda x, p, s, z: quant_matmul(x, p, s, z, bits=2,
                                             group_size=128),
             *_packed_operands(one_chip, 2, 128, M, k=k, n=n))


def test_quant_matmul_experts_compiles(one_chip):
    _compile(lambda x, p, s, z: quant_matmul_experts(x, p, s, z, bits=2,
                                                     group_size=128),
             *_packed_operands(one_chip, 2, 128, 128, n=N_EXPERT, lead=(8,)))


# Moonlight's expert matmuls: gate/up (2048 x 1408) and down (1408 x
# 2048) over 64 experts, at a decode step's rows (64 slots x top-6 in
# tiles of 16) and a 1280-token prefill's (tiles of 128)
@pytest.mark.parametrize("k,n,tm,rows", [
    (2048, 1408, 16, 384), (1408, 2048, 16, 384),
    (2048, 1408, 128, 7680), (1408, 2048, 128, 7680),
], ids=["gate-decode", "down-decode", "gate-prefill", "down-prefill"])
def test_quant_gmm_compiles(one_chip, k, n, tm, rows):
    E = 64
    tiles = -(-rows // tm) + E
    x, p, s, z = _packed_operands(one_chip, 2, 128, tiles * tm, k=k, n=n)
    lead = lambda a: jax.ShapeDtypeStruct((E,) + a.shape, a.dtype,
                                          sharding=one_chip)
    te = jax.ShapeDtypeStruct((tiles,), jnp.int32, sharding=one_chip)
    nt = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _compile(lambda x, p, s, z, te, nt: quant_gmm(
        x, p, s, z, te, nt, bits=2, group_size=128, row_tile=tm),
        x, lead(p), lead(s), lead(z), te, nt)


def test_latent_decode_attention_compiles(one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slots, S, heads, r, rope = 64, 1472, 16, 512, 64
    vec = sds((slots,), jnp.int32)
    _compile(lambda q, c, n, p, a: decode_attention(
        q, c, None, kv_len=n, q_pos=p, active=a, scale=192 ** -0.5,
        chunk=1 << 30, dv=r),
        sds((slots, 1, heads, r + rope), jnp.bfloat16),
        sds((slots, S, r + rope), jnp.bfloat16), vec, vec,
        sds((slots,), jnp.bool_))


@pytest.mark.parametrize("leaf,dtype", [
    pytest.param((32, 32, 1472, 8, 128), jnp.bfloat16, id="mistral"),
    pytest.param((32, 32, 1472, 8, 128), jnp.int8, id="mistral-int8"),
    pytest.param((26, 64, 1472, 576), jnp.bfloat16, id="latent")])
def test_cache_write_compiles(one_chip, leaf, dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    B = leaf[1]
    _compile(lambda c, r, i, p: cache_write(c, r, i, p), sds(leaf, dtype),
             sds((B,) + leaf[3:], dtype), sds((), jnp.int32),
             sds((B,), jnp.int32))


def _attn_operands(one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (sds((B, HKV, G, D), jnp.bfloat16), sds((B,), jnp.int32),
            sds((B,), jnp.int32), sds((B,), jnp.bool_), sds)


def test_stacked_decode_attention_compiles(one_chip):
    """The decode step's call: the stacked leaf and the layer's index, for
    GQA at this file's widths (3 layers) and at Moonlight's latent leaf
    (26 layers x 64 slots x 1472 x 576)."""
    q, kv_len, q_pos, active, sds = _attn_operands(one_chip)
    layer = sds((), jnp.int32)
    kv = sds((3, B, 1024, HKV, D), jnp.bfloat16)
    _compile(lambda q, k, v, n, p, a, i: decode_attention(
        q, k, v, kv_len=n, q_pos=p, active=a, layer=i), q, kv, kv, kv_len,
        q_pos, active, layer)
    slots, heads, width = 64, 16, 576
    vec = sds((slots,), jnp.int32)
    _compile(lambda q, k, n, p, a, i: decode_attention(
        q, k, None, kv_len=n, q_pos=p, active=a, layer=i, chunk=1 << 30,
        dv=512), sds((slots, 1, heads, width), jnp.bfloat16),
        sds((26, slots, 1472, width), jnp.bfloat16), vec, vec,
        sds((slots,), jnp.bool_), layer)


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


def test_quant_gemv_is_one_named_kernel_inside_a_step(one_chip,
                                                     monkeypatch):
    """Each GEMV call inside a larger jitted program is one Mosaic kernel
    named ``quant_gemv_op`` and nothing else of its own: ``x``'s plane
    order is made inside the kernel, so the step holds no extra op per
    call.  The benchmark counts these kernels, one per block linear."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    x, p, s, z = _packed_operands(one_chip, 2, 128, 32, k=4096, n=1024)
    a = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)

    def step(x, p, s, z, a):
        h = ops.quant_gemv_op(x / a, p, s, z, bits=2, group_size=128)
        return ops.quant_gemv_op(x * 2, p, s, z, bits=2, group_size=128) + h

    text = jax.jit(step).lower(x, p, s, z, a).compile().as_text()
    assert _custom_call_names(text) == {"quant_gemv_op"}
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "jit(quant_gemv_op)/" not in text.replace(
        "jit(quant_gemv_op)/pallas_call", "")


def test_quant_matmul_is_one_named_kernel_inside_a_step(one_chip,
                                                       monkeypatch):
    """Each prefill GEMM call of a prompt of a multiple of 16 rows inside
    a larger jitted program is one Mosaic kernel named
    ``quant_matmul_op`` and nothing else of its own (no pad, no slice):
    ``qmm_roofline`` counts these kernels, one per block linear."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    x, p, s, z = _packed_operands(one_chip, 2, 128, 1040, k=4096, n=1024)
    a = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)

    def step(x, p, s, z, a):
        h = ops.quant_matmul_op(x / a, p, s, z, bits=2, group_size=128)
        return ops.quant_matmul_op(x * 2, p, s, z, bits=2,
                                   group_size=128) + h

    text = jax.jit(step).lower(x, p, s, z, a).compile().as_text()
    assert _custom_call_names(text) == {"quant_matmul_op"}
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "jit(quant_matmul_op)/" not in text.replace(
        "jit(quant_matmul_op)/pallas_call", "")


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


def test_cache_write_is_one_named_kernel_that_aliases_the_leaf(one_chip,
                                                              monkeypatch):
    """Inside a step the write is one kernel named ``cache_write_op``, and
    a donated leaf is written in place: the program's temporaries hold no
    second copy of it."""
    from repro.kernels import ops
    from repro.launch.steps import cache_donate_argnums
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    leaf = sds((32, 32, 1472, 8, 128), jnp.bfloat16)

    def step(c, r, i, p):
        return ops.cache_write_op(c, r * 2, i, p), r.sum()

    compiled = jax.jit(step, donate_argnums=cache_donate_argnums(0)).lower(
        leaf, sds((32, 8, 128), jnp.bfloat16), sds((), jnp.int32),
        sds((32,), jnp.int32)).compile()
    assert _custom_call_names(compiled.as_text()) == {"cache_write_op"}
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
