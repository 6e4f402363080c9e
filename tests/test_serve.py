"""Serving launcher: quant-tag parsing, the FP-baseline branch, and
uniform requests through the one serve loop (``serve_scheduled``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.launch.scheduler import Request, serve_scheduled
from repro.launch.serve import main, parse_quant
from repro.models import get_model


# -- parse_quant -------------------------------------------------------------

def test_parse_quant_valid():
    q = parse_quant("W4A16g32")
    assert (q.bits, q.group_size, q.act_bits) == (4, 32, None)
    q = parse_quant("W2A8")
    assert (q.bits, q.group_size, q.act_bits) == (2, None, 8)
    q = parse_quant("W3A16g128", kernel_backend="pallas")
    assert (q.bits, q.group_size) == (3, 128)
    assert q.kernel_backend == "pallas"


@pytest.mark.parametrize("tag", ["w4a16", "W4", "4A16", "W4A16g", "quux",
                                 "W4A16g32x", ""])
def test_parse_quant_malformed(tag):
    with pytest.raises(ValueError, match="malformed quant tag"):
        parse_quant(tag)


def test_quant_tag_roundtrip():
    """QuantConfig.tag is the canonical serialization: parse_quant(q.tag)
    reproduces q exactly, so BENCH/EVAL row keys feed back into the CLI."""
    from repro.configs.base import QuantConfig
    for q in (QuantConfig(bits=4, group_size=32),
              QuantConfig(bits=2, group_size=None, act_bits=8),
              QuantConfig(bits=3, group_size=128),
              QuantConfig(bits=8, group_size=64, act_bits=8),
              QuantConfig(bits=2, group_size=32, act_bits=None)):
        assert parse_quant(q.tag) == q, q.tag
    assert parse_quant("W4A16g32").tag == "W4A16g32"
    assert parse_quant("W2A8").tag == "W2A8"


def test_parse_quant_zero_group():
    with pytest.raises(ValueError, match="group size must be a positive"):
        parse_quant("W4A16g0")


def test_parse_quant_unsupported_bits():
    with pytest.raises(ValueError, match="unsupported weight bits"):
        parse_quant("W5A16g32")


# -- CLI smoke ---------------------------------------------------------------

def test_serve_cli_fp_baseline(capsys):
    """``--method none`` must serve plain params WITHOUT running the
    calibration+pack pipeline (the branch was dead before this fix)."""
    rc = main(["--arch", "tinyllama-1.1b", "--reduced", "--method", "none",
               "--requests", "2", "--prompt-len", "8", "--gen", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "serving FP" in out
    assert "calibrating" not in out


def test_serve_cli_scheduled(capsys):
    """``--slots`` routes serving through the continuous-batching scheduler
    over a seeded heterogeneous workload."""
    rc = main(["--arch", "tinyllama-1.1b", "--reduced", "--method", "none",
               "--requests", "4", "--prompt-len", "8", "--gen", "3",
               "--slots", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "scheduled 4 requests over 2 slots" in out
    assert "latency (decode steps)" in out


@pytest.mark.slow
def test_serve_cli_quantized(capsys):
    rc = main(["--arch", "tinyllama-1.1b", "--reduced", "--method",
               "tesseraq", "--init", "rtn", "--quant", "W4A16g32",
               "--requests", "2", "--prompt-len", "8", "--gen", "2",
               "--par-iters", "1", "--par-steps", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "calibrating" in out


# -- uniform requests --------------------------------------------------------

def _uniform(prompts, gen):
    return [Request(rid=b, prompt=p, max_new_tokens=gen)
            for b, p in enumerate(prompts)]


def test_serve_uniform_shapes_and_accounting():
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    r = serve_scheduled(cfg, params, _uniform(prompts, 3), slots=3,
                        collect_logits=True)
    assert r.token_matrix().shape == (3, 3)
    assert r.logits_matrix().shape == (3, 3, cfg.vocab_size)
    # all arrive at once over one slot each: every step decodes every row
    assert r.steps == 2 and r.occupancy == 1.0
    assert r.useful_tokens == 9 and r.decode_tokens == 6
    # deterministic: same params/prompts -> same generation
    r2 = serve_scheduled(cfg, params, _uniform(prompts, 3), slots=3)
    np.testing.assert_array_equal(r.token_matrix(), r2.token_matrix())


def test_serve_uniform_decode_continues_prefill():
    """The first decode step must see the prefill cache: generating
    token-by-token matches a fresh prefill over prompt+generated."""
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    toks = serve_scheduled(cfg, params, _uniform(prompts, 3),
                           slots=2).token_matrix()
    ext = np.concatenate([prompts, toks[:, :2]], axis=1)
    cache = m.init_cache(2, ext.shape[1] + 1)
    logits2, _ = jax.jit(m.prefill)(params, {"tokens": jnp.asarray(ext)},
                                    cache)
    tok = np.asarray(jnp.argmax(logits2, -1))
    np.testing.assert_array_equal(tok, toks[:, 2])
