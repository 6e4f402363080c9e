"""The one-token decode over a dense store writes its caches in place.

The stacked cache rides in the layer loop's carry
(``models.common.cache_layer_loop``), each layer writes one row a slot
(``models.common.write_rows``) and decode attention reads the stacked leaf
by layer index.  Pinned here, at tiny GQA and latent-attention configs:

  * the carried step gives logits and caches bit-identical to the scan
    over ``xs`` with a per-layer masked write (the layout it replaced,
    rebuilt below from each family's ``block``), under ragged positions,
    inactive slots at ``pos = max_seq``, an int8 cache and the TP=1
    ``shard_map`` step;
  * the decode kernel over a stacked leaf with a layer index equals the
    call on that layer's slice;
  * a row at ``pos = max_seq`` changes no cache leaf;
  * the lowered step (StableHLO) holds no ``select``,
    ``dynamic_update_slice`` or ``concatenate`` of a cache-shaped value:
    the whole-cache rewrite cannot come back unnoticed.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels.ops import decode_attention_op
from repro.launch.mesh import serve_mesh
from repro.launch.steps import (cache_donate_argnums, make_ctx,
                                make_sched_steps)
from repro.models import get_model, layers as L, mla_moe, transformer
from repro.models.common import layer_loop, write_rows

GQA, MLA = "tinyllama-1.1b", "moonlight-16b-a3b"
B, S = 4, 24


def _setup(arch, kv_bits=None):
    cfg = get_reduced_config(arch).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    if model.serve_params:
        params = model.serve_params(params)
    dt = jnp.int8 if kv_bits else jnp.bfloat16
    key = jax.random.PRNGKey(1)
    scale = 20.0 if kv_bits else 1.0
    cache = jax.tree_util.tree_map(
        lambda c: (jax.random.normal(key, c.shape) * scale).astype(c.dtype),
        model.init_cache(B, S, dt))
    return cfg, model, params, cache


def _scan_over_xs(params, cfg, cache, tokens, pos, ctx, active):
    """The decode step with the cache in the layer scan's ``xs``/``ys``
    and each layer's one-token write a masked select over its lane."""
    if cfg.family == "mla_moe":
        x = params["embed"][tokens][:, None, :]

        def step(h, layer):
            bp, c = layer
            h, c, _ = mla_moe.block(bp, h, cfg, ctx, positions=pos[:, None],
                                    cache=c, pos=pos, active=active)
            return h, c

        new = {}
        for key, leaf in mla_moe.STACKS:
            x, new[leaf] = layer_loop(step, x, (params[key], cache[leaf]),
                                      False)
        return mla_moe._unembed(params, cfg, x, ctx)[:, 0], new
    x = transformer.embed_tokens(params, cfg, tokens)[:, None, :]

    def step(h, layer):
        bp, kv = layer
        return transformer.block(bp, h, cfg, ctx, positions=pos[:, None],
                                 kv_cache=kv, cache_pos=pos, kv_len=pos + 1,
                                 active=active)

    x, new = layer_loop(step, x, (params["blocks"], cache), False)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return transformer.unembed(params, cfg, x, ctx)[:, 0], new


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(np.asarray(x.astype(jnp.float32)),
                              np.asarray(y.astype(jnp.float32)))


# ragged live positions, one slot inactive (the scheduler writes it at
# max_seq), one live slot on the lane's last row
TOKENS = jnp.asarray([3, 5, 7, 9], jnp.int32)
POS = jnp.asarray([5, S - 1, S, 17], jnp.int32)
ACTIVE = jnp.asarray([True, True, False, True])


@pytest.mark.parametrize("arch,backend,kv_bits", [
    (GQA, "xla", None), (GQA, "pallas", None), (GQA, "xla", 8),
    (GQA, "pallas", 8), (MLA, "xla", None), (MLA, "pallas", None)])
def test_carried_decode_matches_scan_over_xs(arch, backend, kv_bits):
    cfg, model, params, cache = _setup(arch, kv_bits)
    ctx = make_ctx(cfg, decode=True, attn_chunk=8, remat=False,
                   kernel_backend=backend, kv_bits=kv_bits)
    want = jax.jit(lambda p, c: _scan_over_xs(p, cfg, c, TOKENS, POS, ctx,
                                              ACTIVE))(params, cache)
    got = jax.jit(lambda p, c: model.decode_step(
        p, c, TOKENS, POS, ctx, active=ACTIVE))(params, cache)
    _assert_trees_equal(got, want)


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_tp1_shard_map_decode_matches_scan_over_xs(kv_bits):
    """The TP decode step runs the carried path on local shards inside
    ``shard_map``; at TP=1 it equals the scan over ``xs`` bit for bit."""
    cfg, model, params, cache = _setup(GQA, kv_bits)
    _, _, dec = make_sched_steps(cfg, serve_mesh(tp=1), max_seq=S,
                                 kernel_backend="xla", kv_bits=kv_bits,
                                 decode_attn_chunk=8, tp_shard=True)
    ctx = make_ctx(cfg, decode=True, attn_chunk=8, remat=False,
                   kernel_backend="xla", kv_bits=kv_bits)
    write_pos = jnp.where(ACTIVE, POS, S)
    want = jax.jit(lambda p, c: _scan_over_xs(p, cfg, c, TOKENS, write_pos,
                                              ctx, ACTIVE))(params, cache)
    logits, _, _, new = jax.jit(dec)(params, cache, TOKENS, POS, ACTIVE)
    _assert_trees_equal((logits, new), want)


@pytest.mark.parametrize("latent", [False, True], ids=["gqa", "latent"])
def test_decode_attention_by_layer_index_equals_the_slice(latent):
    Lyr, Hkv, G = 3, (1 if latent else 2), 4
    D = 48 if latent else 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, Hkv, G, D)), jnp.float32)
    kv_shape = (Lyr, B, S, D) if latent else (Lyr, B, S, Hkv, D)
    k = jnp.asarray(rng.normal(size=kv_shape), jnp.bfloat16)
    v = None if latent else jnp.asarray(rng.normal(size=kv_shape),
                                        jnp.bfloat16)
    kv_len = jnp.asarray([6, 24, 1, 13], jnp.int32)
    kw = dict(kv_len=kv_len, q_pos=kv_len - 1, active=ACTIVE, chunk=8,
              dv=32 if latent else None)
    for i in range(Lyr):
        got = decode_attention_op(q, k, v, layer=jnp.int32(i), **kw)
        want = decode_attention_op(q, k[i], None if latent else v[i], **kw)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_write_rows_writes_one_row_a_slot_and_drops_past_the_lane():
    rng = np.random.default_rng(4)
    leaf = jnp.asarray(rng.normal(size=(3, B, S, 2, 8)), jnp.bfloat16)
    rows = jnp.asarray(rng.normal(size=(B, 2, 8)), jnp.float32)
    got = np.asarray(write_rows(leaf, jnp.int32(1), rows, POS), np.float32)
    want = np.asarray(leaf, np.float32)
    for b, p in enumerate(np.asarray(POS)):
        if p < S:
            want[1, b, p] = np.asarray(rows[b].astype(jnp.bfloat16),
                                       np.float32)
    assert np.array_equal(got, want)
    past = write_rows(leaf, jnp.int32(2), rows, jnp.full((B,), S, jnp.int32))
    assert np.array_equal(np.asarray(past, np.float32),
                          np.asarray(leaf, np.float32))


@pytest.mark.parametrize("arch,backend", [
    (GQA, "xla"), (GQA, "pallas"), (MLA, "xla"), (MLA, "pallas")])
def test_rows_past_the_lane_leave_every_cache_leaf_unchanged(arch, backend):
    """Every slot inactive: the scheduler writes all of them at
    ``max_seq``, and no leaf of the cache moves (nothing is clamped onto
    the last row)."""
    cfg, _, params, cache = _setup(arch)
    _, _, dec = make_sched_steps(cfg, max_seq=S, kernel_backend=backend,
                                 decode_attn_chunk=8)
    before = jax.tree_util.tree_map(np.asarray, cache)
    dead = jnp.zeros((B,), bool)
    out = jax.jit(dec)(params, cache, TOKENS, POS, dead)
    _assert_trees_equal(out[3], before)


def _cache_ops(text, shapes):
    """StableHLO lines whose op is a select, dynamic_update_slice or
    concatenate and whose types name one of ``shapes``."""
    ops = re.compile(r"stablehlo\.(select|dynamic_update_slice|concatenate)\b")
    return [line.strip() for line in text.splitlines()
            if ops.search(line) and any(s in line for s in shapes)]


def _tensor(shape, dtype="bf16"):
    return "tensor<" + "x".join(map(str, shape)) + "x" + dtype + ">"


@pytest.fixture
def chip_kernels(monkeypatch):
    """Pallas kernels lowered for the chip (not interpreted).  Traces are
    cached per jitted wrapper and shape, not per mode: clear them on the
    way in and out, so that no interpreted trace is lowered here and no
    chip kernel is run on the CPU by a later test."""
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _lowered_decode_step(arch, backend):
    cfg, model, params, cache = _setup(arch)
    _, _, dec = make_sched_steps(cfg, max_seq=S, kernel_backend=backend)
    traced = jax.jit(dec, donate_argnums=cache_donate_argnums(1)).trace(
        params, cache, TOKENS, POS, ACTIVE)
    platforms = ("tpu",) if backend == "pallas" else None
    return cache, traced.lower(lowering_platforms=platforms).as_text()


def _assert_no_cache_sized_rewrite(cache, text):
    shapes = set()
    for leaf in jax.tree_util.tree_leaves(cache):
        shapes |= {_tensor(leaf.shape), _tensor(leaf.shape[1:])}
    assert _cache_ops(text, shapes) == []


@pytest.mark.parametrize("arch", [GQA, MLA])
def test_lowered_decode_step_rewrites_no_cache_sized_value(arch):
    """The xla backend's step: one scatter a leaf a layer."""
    cache, text = _lowered_decode_step(arch, "xla")
    assert "stablehlo.scatter" in text
    _assert_no_cache_sized_rewrite(cache, text)


@pytest.mark.parametrize("arch", [GQA, MLA])
def test_chip_decode_step_rewrites_no_cache_sized_value(arch, chip_kernels):
    """The pallas backend's step as the chip runs it (Mosaic kernels,
    lowered for the TPU platform without a chip): the write is the aliased
    ``cache_write_op`` kernel."""
    cache, text = _lowered_decode_step(arch, "pallas")
    assert "cache_write_op" in text and "tpu_custom_call" in text
    _assert_no_cache_sized_rewrite(cache, text)

