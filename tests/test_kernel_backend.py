"""The Pallas serving backend must agree with the XLA dequant path — on a
whole packed model's loss, and on the actual serve path (prefill + batched
decode) at every deployed bit-width (deliverable integration gate)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import pack_model, quantize_model
from repro.eval.harness import logits_parity
from repro.models import get_model
from repro.models import layers as L
from repro.models.common import Ctx


def _pack(cfg, m, params, batches, qcfg):
    pq, qmeta, _ = quantize_model(cfg, params, batches, qcfg, method="none",
                                  init="rtn")
    return pack_model(cfg, pq, qmeta, qcfg)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_reduced_config("tinyllama-1.1b").replace(dtype="float32")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                   (2, 16)))}]
    return cfg, m, params, batches


def test_resolve_backend():
    assert L.resolve_backend("xla") == "xla"
    assert L.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        L.resolve_backend("cuda")


def test_resolve_backend_env_fallback(monkeypatch):
    """None defers to the env var, read FRESH each call (never cached)."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert L.resolve_backend(None) == "xla"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert L.resolve_backend(None) == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert L.resolve_backend(None) == "xla"          # no first-call caching


def test_explicit_backend_wins_over_env(monkeypatch, tiny):
    """Ctx plumbing must override the env var: with the env var pointing at
    a bogus backend, an explicit per-call backend still dispatches."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    cfg, m, params, batches = tiny
    qcfg = QuantConfig(bits=4, group_size=32)
    packed = _pack(cfg, m, params, batches, qcfg)
    ctx = Ctx(kernel_backend="xla")
    l_xla = float(m.loss_fn(packed, batches[0], ctx))
    assert np.isfinite(l_xla)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        m.loss_fn(packed, batches[0], Ctx())          # falls through to env


def test_pallas_backend_matches_xla_loss(tiny):
    cfg, m, params, batches = tiny
    qcfg = QuantConfig(bits=4, group_size=32)
    packed = _pack(cfg, m, params, batches, qcfg)
    ctx_xla = Ctx(kernel_backend="xla")
    ctx_pl = Ctx(kernel_backend="pallas")
    l_xla = np.asarray(jax.jit(lambda p, b: m.loss_fn(p, b, ctx_xla))(
        packed, batches[0]), np.float32)
    # eager: pallas interpret mode inside jit-of-scan is slow; eager suffices
    l_pl = np.asarray(m.loss_fn(packed, batches[0], ctx_pl), np.float32)
    np.testing.assert_allclose(l_pl, l_xla, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("bits", [2, 3, 4])
def test_serve_path_backend_parity(tiny, bits):
    """Acceptance gate: prefill + >= 3 continuous-batched decode steps must
    produce matching logits under both backends at W2/W3/W4 (bf16-level
    tolerance — the xla path dequantizes in the activation dtype)."""
    cfg, m, params, batches = tiny
    qcfg = QuantConfig(bits=bits, group_size=32)
    packed = _pack(cfg, m, params, batches, qcfg)
    rng = np.random.default_rng(bits)
    prompts = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    gate = logits_parity(cfg, packed, prompts, gen=4,
                         atol=5e-2, rtol=2e-2)
    assert gate["steps_compared"] == 4                # prefill + 3 decode
    assert gate["ok"], f"W{bits} backend divergence: {gate}"


def test_moe_expert_backend_parity():
    """The MoE expert path (expert_matmul) dispatches per-backend too."""
    cfg = get_reduced_config("qwen3-moe-30b-a3b").replace(dtype="float32")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    batches = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                   (2, 12)))}]
    qcfg = QuantConfig(bits=4, group_size=32)
    packed = _pack(cfg, m, params, batches, qcfg)
    l_xla = float(m.loss_fn(packed, batches[0], Ctx(kernel_backend="xla")))
    l_pl = float(m.loss_fn(packed, batches[0], Ctx(kernel_backend="pallas")))
    np.testing.assert_allclose(l_pl, l_xla, rtol=5e-3, atol=5e-3)


def test_quantconfig_carries_backend():
    qcfg = dataclasses.replace(QuantConfig(), kernel_backend="pallas")
    assert qcfg.kernel_backend == "pallas"
    assert QuantConfig().kernel_backend == "xla"
