"""The comparison that decides ``correct`` catches what it must, on the CPU.

Each test skips the harness's look for a chip and drives the rest of a run
(``bench/run.py``'s ``main``) on the toy cell of ``tiny.py``: a sound
program comes out correct; a program whose decode step alters the tokens it
produces, returns its KV cache unwritten, or leaves half of the slots out,
comes out not correct; and the control (the reference at float8 in place of
the program) fails the limit.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run  # noqa: E402
from bench.drivers.serve import Driver  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 3 * 2**32 + 11


def _run(monkeypatch, capsys, fault=None):
    from repro.launch import compile_cache, scheduler
    from repro.launch.steps import make_sched_steps
    from repro.models.common import write_slot
    spec = tiny.spec()
    monkeypatch.setattr(run, "cell_spec", lambda name: spec)
    monkeypatch.setattr(run, "device_check", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peak_bytes", lambda devs: 0)
    # a CPU test keeps out of the checkout's compilation cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    if fault is not None:
        V = spec["config"]["vocab_size"]

        def broken(cfg, *, max_seq, kernel_backend=None, **_):
            model, pstep, dstep = make_sched_steps(
                cfg, max_seq=max_seq, kernel_backend=kernel_backend)

            def decode(p, c, t, pos, act):
                if fault == "half":          # half of the batch left out
                    act = act & (jnp.arange(act.shape[0]) < act.shape[0] // 2)
                lg, t2, pos2, c2 = dstep(p, c, t, pos, act)
                if fault == "token":         # an answer altered where made
                    return lg, jnp.where(act, (t2 + 1) % V, t2), pos2, c2
                if fault == "state":         # the step leaves its state
                    return lg, t2, pos2, c
                return lg, t2, pos2, c2
            return scheduler.SchedSteps(
                model=model, prefill=jax.jit(pstep), decode=jax.jit(decode),
                write_slot=jax.jit(write_slot))
        monkeypatch.setattr(scheduler, "compile_sched_steps", broken)
    assert run.main(["--workload", tiny.CELL, "--seed", str(SEED),
                     "--seconds", "0", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_program_is_correct(monkeypatch, capsys):
    out = _run(monkeypatch, capsys)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 6
    assert set(out["metrics"]) == {"out_tok_s", "tpot_ms", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_broken_decode_is_not_correct(monkeypatch, capsys, fault):
    out = _run(monkeypatch, capsys, fault)
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_control_fails_the_limit(seed):
    """The reference at float8 put in the program's place fails the
    configuration's own limit: at each position of the same prompts and
    tokens, the token float8 ranks first lies further below the float32
    reference's best than the limit allows.  The model here is cut to what
    a CPU test holds (8 layers, d_model 512) at the published vocabulary;
    on the chip the control reads higher still (PERF.md)."""
    import numpy as np

    from bench import traffic
    spec = tiny.spec()
    limit = json.loads((ROOT / "bench" / "configs" / "mistral-7b-w2g128.json")
                       .read_text())["check"]["max_logit_gap"]
    spec["config"].update(hidden_size=512, intermediate_size=1024,
                          num_attention_heads=4, num_hidden_layers=8,
                          vocab_size=32000)
    mix = dict(spec["mix"], prompt_lens=[64, 96], budget_range=[4, 64])
    d = Driver(spec["config"], mix, seed)
    rng = np.random.default_rng(seed)
    picked = [(0, p, rng.integers(0, 32000, 64).astype(np.int32))
              for p in traffic.wave(mix, 32000, seed, 0)[:3]]
    gaps = d.logit_gaps(picked, precision="fp8", chosen="reference")
    assert gaps.max() > limit
