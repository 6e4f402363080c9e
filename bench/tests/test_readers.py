"""The per-layer readers that read the serve loop's spans and the decode
attention kernel, on synthetic traces and on one traced run of the toy
cell, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_readers.py
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import costs, run, trace  # noqa: E402
from bench.drivers.serve import Window, model_sizes  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.traffic import Planned  # noqa: E402

PLANE = "/device:TPU:0"
V5E = PEAKS["TPU v5 lite"]
NEW = ["attn_roofline", "decode_rest_ms", "admit_idle_ms", "dispatch_ms",
       "fetch_ms"]


def reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def window(requests, steps_per_wave):
    """A window of waves: ``requests[k]`` is wave k's [(plen, budget)]."""
    waves, rid = [], 0
    for reqs, steps in zip(requests, steps_per_wave):
        plan, served = [], {}
        for plen, budget in reqs:
            plan.append(Planned(rid=rid, prompt=np.zeros(plen, np.int32),
                                max_new_tokens=budget, arrival=0))
            served[rid] = {"tokens": np.zeros(budget, np.int32)}
            rid += 1
        waves.append((plan, SimpleNamespace(steps=steps, requests=served)))
    return Window(wall_s=1.0, waves=waves)


def context(win, *, ops=(), modules=(), host=()):
    tr = trace.Trace(device_ops={PLANE: list(ops)},
                     modules={PLANE: list(modules)},
                     host=[(trace.WINDOW_SPAN, 0, 10**6)] + list(host))
    cfg = tiny.spec()["config"]
    return {"window": win, "trace": tr, "peaks": V5E,
            "m": model_sizes(cfg), "q": cfg["quant"], "costs": costs}


# -- names ---------------------------------------------------------------------

def test_decode_attention_name_from_the_chips_trace():
    """The attention kernel's event as a v5e trace gives it, under its
    jitted wrapper's name."""
    text = ("%decode_attention_op.7 = bf16[32,8,4,128]{3,2,1,0:T(8,128)(2,1)}"
            " custom-call(s32[32]{0} %convert.11, bf16[32,8,4,128]{3,2,1,0} "
            "%bitcast.2), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "decode_attention_op"
    assert not trace._is_container(text)


def test_new_metrics_are_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [
            "mistral7b-w2-decode", "mistral7b-w2-prefill"]
        assert (ROOT / "bench" / "metrics" / f"{name}.py").exists()


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_without_spans_or_kernel_names(name):
    """A trace from a program without the spans and the named attention
    kernel (the window span and some device work only) reads None."""
    win = window([[(4, 3)]], [2])
    ops = [("fusion", 10, 20), ("closed_call", 30, 40)]
    mods = [("jit_sched_decode_step", 5, 45), ("jit_sched_decode_step", 50, 60)]
    assert reader(name)(context(win, ops=ops, modules=mods)) is None
    assert reader(name)(dict(context(win), trace=None)) is None


# -- attn_roofline ---------------------------------------------------------------

def _attn_least(m, reqs):
    """Least seconds of decode attention, slot-step by slot-step."""
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    total = 0.0
    for plen, budget in reqs:
        for j in range(1, budget):
            n = plen + j
            flops = 2 * 2 * hq * d * n            # q.k and p.v, per position
            nbytes = 2 * (2 * n * hkv * d + 2 * hq * d)
            total += max(flops / V5E["bf16_flops"],
                         nbytes / V5E["hbm_bytes_s"])
    return m["num_hidden_layers"] * total


def test_attn_roofline_arithmetic():
    reqs = [(3, 3), (5, 2), (7, 1)]
    steps = 2
    ctx = context(window([reqs], [steps]))
    L = ctx["m"]["num_hidden_layers"]
    ops = [
        ("decode_attention_op", 100 * i, 100 * i + 40)
        for i in range(L * steps)] + [("fusion", 5000, 5100)]
    ctx["trace"].device_ops[PLANE] = ops
    want = 100.0 * _attn_least(ctx["m"], reqs) / (L * steps * 40e-9)
    assert reader("attn_roofline")(ctx) == pytest.approx(want)
    # one event short of one per layer per step reads nothing
    ctx["trace"].device_ops[PLANE] = ops[1:]
    assert reader("attn_roofline")(ctx) is None


def test_attention_is_memory_bound_at_mistral_widths():
    """At the configuration's widths every slot-step's FLOPs per byte sit
    far below the v5e's ridge, so its bytes bound it."""
    mod = run.load_module(ROOT / "bench" / "metrics" / "attn_roofline.py")
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "mistral-7b-w2g128.json").read_text())
    m = model_sizes(cfg)
    ridge = V5E["bf16_flops"] / V5E["hbm_bytes_s"]
    for plen in (1, 768, 3072):
        for flops, nbytes in mod.slot_steps(m, plen, 3):
            assert flops / nbytes < 4 < ridge / 50


# -- decode_rest_ms --------------------------------------------------------------

def test_decode_rest_arithmetic():
    ctx = context(window([[(4, 3), (6, 2)]], [2]))
    m, q = ctx["m"], ctx["q"]
    gemvs = len(costs.qlinear_calls(m, 1, q["bits"], q["group_size"]))
    L = m["num_hidden_layers"]
    runs = [(1000, 2000), (3000, 4000)]
    ops = []
    for lo, _ in runs:
        ops += [("quant_gemv_op", lo + 10 * i, lo + 10 * i + 2)
                for i in range(gemvs)]
        ops += [("decode_attention_op", lo + 500 + 10 * i,
                 lo + 500 + 10 * i + 3) for i in range(L)]
    # kernels outside the decode program's executions do not count
    ops += [("quant_gemv_op", 2500, 2600), ("decode_attention_op", 10, 20)]
    mods = [("jit_sched_decode_step", s, e) for s, e in runs] + \
        [("jit_prefill_step", 2400, 2900)]
    ctx["trace"].device_ops[PLANE] = ops
    ctx["trace"].modules[PLANE] = mods
    rest_ns = (2 * 1000 - 2 * (gemvs * 2 + L * 3)) / 2
    assert reader("decode_rest_ms")(ctx) == pytest.approx(rest_ns * 1e-6)
    # a missing attention event, a missing GEMV or a missing execution
    # reads nothing
    for drop in ("decode_attention_op", "quant_gemv_op"):
        i = next(k for k, o in enumerate(ops) if o[0] == drop)
        ctx["trace"].device_ops[PLANE] = ops[:i] + ops[i + 1:]
        assert reader("decode_rest_ms")(ctx) is None
    ctx["trace"].device_ops[PLANE] = ops
    ctx["trace"].modules[PLANE] = mods[1:]
    assert reader("decode_rest_ms")(ctx) is None


# -- admit_idle_ms ---------------------------------------------------------------

def test_admit_idle_arithmetic():
    ops = [("fusion", 0, 100), ("jit_prefill", 300, 500), ("copy", 800, 990)]
    host = [("serve.admit", 50, 350), ("serve.admit", 600, 700),
            ("serve.decode", 500, 800)]
    # idle [100, 300) and [500, 800): 200 inside the first admission, 100
    # inside the second
    ctx = context(window([[(4, 2), (5, 2)]], [3]), ops=ops, host=host)
    ctx["trace"].host[0] = (trace.WINDOW_SPAN, 0, 1000)
    assert reader("admit_idle_ms")(ctx) == pytest.approx(300e-6 / 2)
    # a loop container counts as busy, as in idle_share.serve
    ctx["trace"].containers[PLANE] = [("while", 150, 200)]
    assert reader("admit_idle_ms")(ctx) == pytest.approx(250e-6 / 2)
    ctx["window"] = window([[(4, 2), (5, 2), (6, 2)]], [3])
    assert reader("admit_idle_ms")(ctx) is None


# -- dispatch_ms, fetch_ms ---------------------------------------------------------

def test_dispatch_arithmetic():
    host = [("serve.decode", 100 * i, 100 * i + 10 * (i + 1))
            for i in range(4)] + [("serve.admit", 0, 500)]
    ctx = context(window([[(4, 3)], [(4, 3)]], [1, 3]), host=host)
    assert reader("dispatch_ms")(ctx) == pytest.approx(100e-6 / 4)
    ctx["window"] = window([[(4, 3)], [(4, 3)]], [2, 3])
    assert reader("dispatch_ms")(ctx) is None


def test_fetch_arithmetic():
    host = [("serve.fetch", 1000, 31000), ("serve.fetch", 50000, 60000)]
    ctx = context(window([[(4, 3)], [(4, 3)]], [2, 2]), host=host)
    assert reader("fetch_ms")(ctx) == pytest.approx(40000e-6 / 2)
    ctx["window"] = window([[(4, 3)], [(4, 3)], [(4, 3)]], [2, 2, 2])
    assert reader("fetch_ms")(ctx) is None


def test_spans_outside_the_window_do_not_count():
    host = [("serve.fetch", 1000, 2000), ("serve.fetch", 2 * 10**6, 3 * 10**6)]
    ctx = context(window([[(4, 3)]], [2]), host=host)
    assert reader("fetch_ms")(ctx) == pytest.approx(1000e-6)


# -- one traced run of the toy cell ----------------------------------------------

def test_traced_toy_run_reads_the_host_spans(monkeypatch, capsys, tmp_path):
    """``bench/run.py --trace 1`` on the toy cell: the host-span readers read
    and their counts hold; the device readers, with no TPU plane in a CPU
    trace, read nothing and raise nothing."""
    import jax

    from bench import peaks
    from repro.launch import compile_cache
    spec = tiny.spec()
    monkeypatch.setattr(run, "cell_spec", lambda name: spec)
    monkeypatch.setattr(run, "device_check", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peak_bytes", lambda devs: 0)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(peaks, "peaks", lambda kind: V5E)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    assert run.main(["--workload", tiny.CELL, "--seed", str(2**33 + 5),
                     "--seconds", "0", "--trace", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    got = out["metrics"]
    assert got["dispatch_ms"]["value"] > 0 and got["fetch_ms"]["value"] > 0
    for name in ("attn_roofline", "decode_rest_ms", "admit_idle_ms"):
        assert name not in got
