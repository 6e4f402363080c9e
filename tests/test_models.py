"""Per-architecture smoke tests + the cache-consistency property:
decode_step(prefill(tokens[:-1]), tokens[-1]) must reproduce
forward(tokens) at the last position for EVERY family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced_config
from repro.models import get_model

B, S = 2, 24


def make_batch(cfg, rng, seq=S):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, seq)))}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_len, cfg.d_model)) * 0.1,
            jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_patches, cfg.d_model)) * 0.1,
            jnp.dtype(cfg.dtype))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_loss_and_shapes(arch):
    cfg = get_reduced_config(arch)
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    loss = jax.jit(m.loss_fn)(params, make_batch(cfg, rng))
    assert np.isfinite(float(loss)), arch
    assert float(loss) < 2 * np.log(cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    """KV caches / recurrent states must agree with the cache-free forward."""
    cfg = get_reduced_config(arch).replace(dtype="float32")
    if cfg.moe is not None:
        # exact equivalence requires no capacity drops (token-count dependent)
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=64.0))
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    batch = make_batch(cfg, rng)
    tokens = batch["tokens"]

    # full forward logits
    fam = cfg.family
    if fam in ("dense", "moe"):
        from repro.models import transformer as T
        full = T.forward(params, cfg, tokens)
    elif fam == "mla_moe":
        from repro.models import mla_moe as MM
        full = MM.forward(params, cfg, tokens)
    elif fam == "rwkv":
        from repro.models import rwkv as R
        full = R.forward(params, cfg, tokens)
    elif fam == "hybrid":
        from repro.models import hybrid as H
        full = H.forward(params, cfg, tokens)
    elif fam == "encdec":
        from repro.models import encdec as E
        full = E.forward(params, cfg, batch["frames"], tokens)
    elif fam == "vlm":
        from repro.models import vlm as V
        full = V.forward(params, cfg, batch["patches"], tokens)
    full_last = np.asarray(full[:, -1], np.float32)

    # prefill on all but the final token, then one decode step
    pre_batch = dict(batch, tokens=tokens[:, :-1])
    prefix = cfg.num_patches if fam == "vlm" else 0
    cache = m.init_cache(B, S + prefix + 8, dtype=jnp.float32)
    logits_p, cache = jax.jit(m.prefill)(params, pre_batch, cache)
    pos = jnp.full((B,), S - 1 + prefix, jnp.int32)
    logits_d, _ = jax.jit(m.decode_step)(params, cache, tokens[:, -1], pos)
    got = np.asarray(logits_d, np.float32)

    np.testing.assert_allclose(got, full_last, rtol=2e-3, atol=2e-3)


def test_moe_routing_matches_dense_dispatch():
    """Capacity dispatch with ample capacity == explicit per-token top-k."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import init_moe_ffn, moe_ffn, _route
    from repro.models.common import DEFAULT_CTX
    import dataclasses
    cfg = get_reduced_config("qwen3-moe-30b-a3b")
    cfg = cfg.replace(moe=MoEConfig(num_experts=4, top_k=2,
                                    capacity_factor=8.0))
    mp0 = init_moe_ffn(cfg, jax.random.PRNGKey(0), 1)
    mp = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32), mp0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)) * .3, jnp.float32)
    got = np.asarray(moe_ffn(mp, x, cfg, DEFAULT_CTX), np.float32)

    x2 = np.asarray(x).reshape(-1, cfg.d_model)
    idx, gate = _route(jnp.asarray(x2), mp["router"], 2)
    idx, gate = np.asarray(idx), np.asarray(gate)
    want = np.zeros_like(x2)
    wg, wu, wd = (np.asarray(mp[k], np.float32)
                  for k in ("w_gate", "w_up", "w_down"))
    for t in range(x2.shape[0]):
        for j in range(2):
            e = idx[t, j]
            h = x2[t]
            a = (h @ wg[e])
            a = a / (1 + np.exp(-a)) * (h @ wu[e])
            want[t] += gate[t, j] * (a @ wd[e])
    np.testing.assert_allclose(got.reshape(-1, cfg.d_model), want,
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_vs_naive():
    from repro.models.layers import flash_attention
    rng = np.random.default_rng(0)
    Bq, Sq, Sk, Hq, Hkv, D = 2, 16, 24, 6, 3, 8
    q = jnp.asarray(rng.normal(size=(Bq, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(Bq, Sk, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(Bq, Sk, Hkv, D)), jnp.float32)

    def naive(q, k, v, q_offset):
        G = Hq // Hkv
        kk = jnp.repeat(k, G, axis=2)
        vv = jnp.repeat(v, G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * (D ** -0.5)
        m = (jnp.arange(Sk)[None, :] <= (q_offset + jnp.arange(Sq))[:, None])
        s = jnp.where(m[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    for off in (0, 8):
        got = flash_attention(q, k, v, chunk=7, q_offset=off)
        want = naive(q, k, v, off)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        g1 = jax.grad(lambda *a, off=off: flash_attention(*a, chunk=7,
                                                          q_offset=off).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a, off=off: naive(*a, off).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
