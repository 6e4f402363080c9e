"""The latent-attention expert cell's own pieces, on the CPU at toy widths:
the weights generator against ``pack_model``'s tree, the benchmark's plain
reference against the program's (``repro.models.reference``), the float8
control against the configured limit, the comparison that decides
``correct`` on a sound and a broken program, and the cell's per-layer
readers.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_mla_moe_bench.py
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import costs_mla_moe as C  # noqa: E402
from bench import run, trace  # noqa: E402
from bench import weights_mla_moe as W  # noqa: E402
from bench.drivers.serve import Window  # noqa: E402
from bench.drivers.serve_mla_moe import Driver, program_config  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.reference import mla_moe_lm  # noqa: E402
from bench.tests import tiny_mla_moe as tiny  # noqa: E402
from bench.traffic import Planned  # noqa: E402

SEED = 5 * 2**32 + 3          # wider than 32 bits, as a run's seed may be
PLANE = "/device:TPU:0"
V5E = PEAKS["TPU v5 lite"]
METRICS = ["decode_step_ms.mla", "expert_ms", "gmm_roofline",
           "latent_attn_roofline", "experts_touched", "mfu.decode.mla"]


def _toy():
    return tiny.spec()["config"]


def _shapes(tree):
    from repro.core.qtensor import QTensor
    leaves, tdef = jax.tree_util.tree_flatten(tree)
    aux = [(x.bits, x.group_size, x.shape) for x in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda t: isinstance(t, QTensor))
        if isinstance(x, QTensor)]
    return tdef, [(a.shape, a.dtype) for a in leaves], aux


# -- weights ---------------------------------------------------------------------

def test_weights_tree_is_pack_models():
    """The generated tree has pack_model's structure, leaf shapes, dtypes
    and QTensor layouts for the same configuration, AWQ-initialised."""
    from repro.configs.base import QuantConfig
    from repro.core import pack_model, quantize_model
    from repro.models import get_model
    cfg = _toy()
    pcfg = program_config(cfg)
    params = get_model(pcfg).init_params(jax.random.PRNGKey(0))
    calib = [{"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 16)))}]
    qcfg = QuantConfig(bits=2, group_size=128)
    pq, meta, _ = quantize_model(pcfg, params, calib, qcfg, method="none",
                                 init="awq")
    want = pack_model(pcfg, pq, meta, qcfg)
    got = W.make_packed_params(SEED, W.sizes(cfg), cfg["quant"],
                               cfg["weights"])
    assert _shapes(got) == _shapes(want)


@pytest.mark.parametrize("layer", [0, 2])
def test_one_layer_is_bit_identical_to_its_slice(layer):
    """The dense layer (0) and an expert layer (2) regenerated alone equal
    their slices of the stacked tree."""
    cfg = _toy()
    m, q, w = W.sizes(cfg), cfg["quant"], cfg["weights"]
    tree = W.make_packed_params(SEED, m, q, w)
    one = W.one_layer(SEED, layer, m, q, w)
    n0 = m["first_k_dense_replace"]
    stack, i = (tree["dense_blocks"], layer) if layer < n0 else (
        tree["blocks"], layer - n0)
    pairs = [(one["ln1"], stack["ln1"][i]), (one["kv_norm"],
                                             stack["kv_norm"][i])]
    for name in W.ATTN:
        for f in ("packed", "scale", "zero", "act_scale"):
            pairs.append((one[name][f], getattr(stack[name], f)[i]))
    if layer >= n0:
        moe = stack["moe"]
        pairs += [(one["router"], moe["router"][i]),
                  (one["bias"], moe["bias"][i])]
        for name in W.FFN:
            for f in ("packed", "scale", "zero", "act_scale"):
                pairs.append((one["experts"][name][f],
                              getattr(moe[name], f)[i]))
                pairs.append((one["shared"][name][f],
                              getattr(moe["shared"][name], f)[i]))
    for a, b in pairs:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- references -------------------------------------------------------------------

def test_bench_reference_agrees_with_the_programs_reference():
    """Two plain forwards written apart (one from the seed, layer by layer;
    one over the program's packed tree) agree to float32 rounding; given
    its own routing the bench reference gives the same logits and no
    deficit, and given another routing a deficit."""
    from repro.models.reference import mla_moe_logits
    cfg = _toy()
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                             (2, 24)).astype(np.int32)
    a, used, deficit = (np.asarray(x) for x in mla_moe_lm.logits(
        SEED, cfg, toks))
    assert not deficit.any()
    a2, _, d2 = mla_moe_lm.logits(SEED, cfg, toks, experts=used)
    np.testing.assert_array_equal(np.asarray(a2), a)
    assert not np.asarray(d2).any()
    # position 3 routed to its experts' neighbours: a set other than the
    # top-k, so one of them scores below the k-th
    other = used.copy()
    other[:, :, 3] = (used[:, :, 3] + 1) % cfg["n_routed_experts"]
    _, _, d3 = mla_moe_lm.logits(SEED, cfg, toks, experts=other)
    assert np.asarray(d3)[:, :, 3].max() > 0
    tree = W.make_packed_params(SEED, W.sizes(cfg), cfg["quant"],
                                cfg["weights"])
    b = np.asarray(mla_moe_logits(tree, program_config(cfg), toks))
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * np.abs(a).max())


def test_control_fails_the_limit():
    """The reference at float8 in the program's place (its own tokens and
    routing, judged by the float32 reference given that routing) fails the
    configuration's own limits: the token float8 ranks first lies further
    below the float32 reference's best than ``max_logit_gap`` allows, and
    its expert choices lie further below the float32 scores than
    ``max_route_deficit`` allows.  Toy widths at the published depth,
    experts and vocabulary; the chip's readings are in PERF.md."""
    from bench import traffic
    spec = tiny.spec()
    limits = json.loads((ROOT / "bench" / "configs" /
                         "moonlight-16b-a3b-w2g128.json").read_text()
                        )["check"]
    V = 163840
    spec["config"].update(vocab_size=V, num_hidden_layers=27,
                          n_routed_experts=64, num_experts_per_tok=6)
    mix = dict(spec["mix"], prompt_lens=[48, 64], budget_range=[4, 32])
    d = Driver(spec["config"], mix, SEED)
    rng = np.random.default_rng(SEED)
    none = np.zeros((26, 0, 6), np.int32)
    picked = [(0, p, rng.integers(0, V, 32).astype(np.int32), none)
              for p in traffic.wave(mix, V, SEED, 0)[:3]]
    gaps, deficits = d.compare(picked, precision="fp8", chosen="reference")
    assert gaps.max() > limits["max_logit_gap"]
    assert deficits.max() > limits["max_route_deficit"]


# -- the comparison that decides correct ------------------------------------------

def _run(monkeypatch, capsys, fault=None):
    from repro.launch import compile_cache, scheduler
    from repro.launch.steps import make_sched_steps
    from repro.models.common import write_slot
    spec = tiny.spec()
    monkeypatch.setattr(run, "cell_spec", lambda name: spec)
    monkeypatch.setattr(run, "device_check", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peak_bytes", lambda devs: 0)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    if fault is not None:
        V = spec["config"]["vocab_size"]

        def broken(cfg, *, max_seq, kernel_backend=None, **_):
            model, pstep, dstep = make_sched_steps(
                cfg, max_seq=max_seq, kernel_backend=kernel_backend)

            def decode(p, c, t, pos, act):
                lg, t2, pos2, c2, counters = dstep(p, c, t, pos, act)
                if fault == "token":         # an answer altered where made
                    t2 = jnp.where(act, (t2 + 1) % V, t2)
                if fault == "state":         # the step leaves its state
                    c2 = c
                return lg, t2, pos2, c2, counters
            return scheduler.SchedSteps(
                model=model, prefill=jax.jit(pstep), decode=jax.jit(decode),
                write_slot=jax.jit(write_slot))
        monkeypatch.setattr(scheduler, "compile_sched_steps", broken)
    assert run.main(["--workload", tiny.CELL, "--seed", str(SEED),
                     "--seconds", "0", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, "token", "state"])
def test_correct_catches_a_broken_decode(monkeypatch, capsys, fault):
    """The configuration's limits pass the sound program and fail one whose
    decode step alters the tokens it produces or leaves its cache
    unwritten."""
    out = _run(monkeypatch, capsys, fault)
    gap = out["checks"]["max_logit_gap"]
    assert out["failed"] == 0 and out["attempted"] == 6
    assert out["correct"] is (fault is None), out["checks"]
    assert (gap["value"] <= gap["limit"]) is (fault is None)


# -- per-layer readers -------------------------------------------------------------

def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _window(reqs, steps, layers, touched=3):
    plan = [Planned(rid=i, prompt=np.zeros(p, np.int32), max_new_tokens=b,
                    arrival=0) for i, (p, b) in enumerate(reqs)]
    res = SimpleNamespace(
        steps=steps, slots=4, occupancy=0.5,
        requests={i: {"tokens": np.zeros(b, np.int32)}
                  for i, (_, b) in enumerate(reqs)},
        step_counters={"experts_touched": np.full((steps, layers), touched,
                                                  np.int32),
                       "largest_group": np.ones((steps, layers), np.int32)})
    return Window(wall_s=1.0, waves=[(plan, res)])


def _context(win, ops=(), modules=()):
    cfg = _toy()
    tr = trace.Trace(device_ops={PLANE: list(ops)},
                     modules={PLANE: list(modules)},
                     host=[(trace.WINDOW_SPAN, 0, 10**7)])
    return {"window": win, "trace": tr, "peaks": V5E, "m": W.sizes(cfg),
            "q": cfg["quant"], "mix": tiny.spec()["mix"], "costs": C}


def _step_trace(m, steps, *, gmm_per_step=None, attn_per_step=None):
    """Decode executions 1000 ns apart (each 800 ns), kernels inside them,
    and the same kernels inside a prefill outside them."""
    L, n0 = m["num_hidden_layers"], m["first_k_dense_replace"]
    gmm = 3 * (L - n0) if gmm_per_step is None else gmm_per_step
    attn = L if attn_per_step is None else attn_per_step
    mods, ops = [("jit_prefill_step", 0, 900)], [("quant_gmm_op", 10, 20)]
    for s in range(steps):
        t0 = 1000 * (s + 1)
        mods.append(("jit_sched_decode_step", t0, t0 + 800))
        ops += [("quant_gmm_op", t0 + 10 * i, t0 + 10 * i + 5)
                for i in range(gmm)]
        ops += [("decode_attention_op", t0 + 400 + 10 * i,
                 t0 + 400 + 10 * i + 4) for i in range(attn)]
    return ops, mods


def test_readers_arithmetic():
    cfg = _toy()
    m = W.sizes(cfg)
    reqs, steps = [(8, 3), (16, 4)], 3
    L, n0 = m["num_hidden_layers"], m["first_k_dense_replace"]
    ops, mods = _step_trace(m, steps)
    ctx = _context(_window(reqs, steps, L - n0), ops, mods)
    assert reader("decode_step_ms.mla")(ctx) == pytest.approx(800e-6)
    assert reader("expert_ms")(ctx) == pytest.approx(
        3 * (L - n0) * 5e-6)
    assert reader("experts_touched")(ctx) == pytest.approx(
        100.0 * 3 / m["n_routed_experts"])
    rows = 0.5 * 4 * m["num_experts_per_tok"]
    least = C.roofline_seconds(
        C.gmm_calls(m, cfg["quant"], [3] * (steps * (L - n0)), rows),
        V5E["bf16_flops"], V5E["hbm_bytes_s"])
    assert reader("gmm_roofline")(ctx) == pytest.approx(
        100.0 * least / (steps * 3 * (L - n0) * 5e-9))
    least = L * sum(C.roofline_seconds(C.latent_attn_calls(m, p, b),
                                       V5E["bf16_flops"], V5E["hbm_bytes_s"])
                    for p, b in reqs)
    assert reader("latent_attn_roofline")(ctx) == pytest.approx(
        100.0 * least / (steps * L * 4e-9))
    flops = sum(C.decode_flops(m, p, b) for p, b in reqs)
    assert reader("mfu.decode.mla")(ctx) == pytest.approx(
        100.0 * flops / (steps * 800e-9) / V5E["bf16_flops"])


@pytest.mark.parametrize("name", METRICS)
def test_readers_read_nothing_when_counts_mismatch(name):
    """One event short (or a program without the counters) reads None."""
    cfg = _toy()
    m = W.sizes(cfg)
    L, n0 = m["num_hidden_layers"], m["first_k_dense_replace"]
    steps = 2
    ops, mods = _step_trace(m, steps, gmm_per_step=3 * (L - n0) - 1,
                            attn_per_step=L - 1)
    win = _window([(8, 3)], steps, L - n0)
    if name == "experts_touched":
        win.waves[0][1].step_counters = {}
    elif name in ("decode_step_ms.mla", "mfu.decode.mla"):
        mods = mods[:-1]
    assert reader(name)(_context(win, ops, mods)) is None
    assert reader(name)(dict(_context(win), trace=None,
                             window=_window([(8, 3)], 0, L - n0))) is None
