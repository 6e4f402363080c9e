"""Self-tests of the benchmark's own arithmetic, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import costs, peaks, traffic, trace  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.drivers.serve import model_sizes  # noqa: E402
from bench.reference import dense_lm  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 2**33 + 17        # wider than 32 bits, as the driver's seeds are


# -- traffic -----------------------------------------------------------------

def test_traffic_is_a_pure_function_of_the_seed():
    mix = tiny.spec()["mix"]
    a = traffic.wave(mix, 512, SEED, 3)
    b = traffic.wave(mix, 512, SEED, 3)
    assert [(p.rid, p.max_new_tokens, p.arrival) for p in a] == \
        [(p.rid, p.max_new_tokens, p.arrival) for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix_name", ["tiny", "conversation", "long-prompt"])
def test_every_seed_sends_the_same_work(mix_name):
    import json
    mix = (tiny.spec()["mix"] if mix_name == "tiny" else json.loads(
        (ROOT / "bench" / "traffic" / f"{mix_name}.json").read_text()))

    def sizes(seed, k):
        return [(len(p.prompt), p.max_new_tokens, p.arrival)
                for p in traffic.wave(mix, 32000, seed, k)]

    # the same requests in size and time for every seed ...
    for k in (0, 1):
        assert sizes(1, k) == sizes(SEED, k) == sizes(2**40, k)
    # ... and the same multiset of them in every wave
    a, b = sizes(1, 0), sizes(1, 1)
    assert sorted(x[0] for x in a) == sorted(x[0] for x in b)
    assert sorted(x[1] for x in a) == sorted(x[1] for x in b)
    assert sorted(np.diff([x[2] for x in a]).tolist()) == \
        sorted(np.diff([x[2] for x in b]).tolist())
    w = traffic.wave(mix, 32000, 5, 0)
    assert len(w) == mix["wave_requests"]
    longest = max(len(p.prompt) + p.max_new_tokens for p in w)
    assert longest == traffic.max_positions(mix)
    width = traffic.width(mix)
    assert width % traffic.WIDTH_MULTIPLE == 0
    assert longest <= width < longest + traffic.WIDTH_MULTIPLE
    assert traffic.wave(mix, 32000, 5, 0)[0].prompt.tolist() != \
        traffic.wave(mix, 32000, 6, 0)[0].prompt.tolist()


def test_driver_refuses_a_width_past_the_sliding_window():
    from bench.drivers.serve import Driver
    s = tiny.spec()
    cfg = dict(s["config"], sliding_window=traffic.width(s["mix"]))
    Driver(cfg, s["mix"], SEED)
    cfg["sliding_window"] -= 1
    with pytest.raises(ValueError, match="sliding_window"):
        Driver(cfg, s["mix"], SEED)


def test_poisson_quantiles_mean():
    q = traffic._poisson_quantiles(2.0, 4000)
    assert abs(np.mean(q) - 2.0) < 0.01
    assert q == sorted(q)


# -- peaks -------------------------------------------------------------------

def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


# -- weights, against the program's own packer --------------------------------

def _pack_model_tree(m, q, leaves):
    """``pack_model`` on a one-layer cut, fed the generator's codes and
    scales as its quantization metadata."""
    from repro.core import pack_model
    from repro.core.qtensor import unpack
    from repro.configs.base import QuantConfig
    from repro.models import get_model
    from bench.drivers.serve import program_config
    cfg = dict(tiny.spec()["config"], **m)
    pcfg = program_config(cfg)
    params = jax.eval_shape(get_model(pcfg).init_params, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                    params)
    qmeta = {}
    for name in W.LINEARS:
        leaf = leaves[name]
        K = W.linear_shapes(m)[name][0]
        qmeta[("blocks", 0, name)] = {
            "codes": np.asarray(unpack(leaf["packed"], q["bits"], K)),
            "scale": np.asarray(leaf["scale"]),
            "zero": np.asarray(leaf["zero"]),
            "act_scale": np.asarray(leaf["act_scale"])}
    qc = QuantConfig(bits=q["bits"], group_size=q["group_size"])
    return pack_model(pcfg, params, qmeta, qc)


def test_generator_tree_is_pack_models_tree():
    s = tiny.spec()
    m = dict(model_sizes(s["config"]), num_hidden_layers=1)
    q, w = s["config"]["quant"], s["config"]["weights"]
    ours = W.make_packed_params(SEED, m, q, w)
    leaves = W.one_layer(SEED, 0, m, q, w)
    theirs = _pack_model_tree(m, q, leaves)
    ta, tb = (jax.tree_util.tree_structure(t) for t in (ours, theirs))
    assert ta == tb
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # the program's packer re-packs the generator's codes to the same bytes
    for name in W.LINEARS:
        np.testing.assert_array_equal(ours["blocks"][name].packed,
                                      theirs["blocks"][name].packed)
    # and bytes counted from shapes equal the program's own count
    from repro.core.qtensor import QTensor
    qts = [x for x in jax.tree_util.tree_leaves(
        theirs, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(x, QTensor)]
    assert sum(x.memory_bytes() for x in qts) == sum(
        costs.weight_bytes(K, N, q["bits"], q["group_size"])
        for K, N in W.linear_shapes(m).values())


def test_one_layer_is_its_slice_of_the_stacked_tree():
    s = tiny.spec()
    m = model_sizes(s["config"])
    q, w = s["config"]["quant"], s["config"]["weights"]
    tree = W.make_packed_params(SEED, m, q, w)
    for layer in range(m["num_hidden_layers"]):
        one = W.one_layer(SEED, layer, m, q, w)
        for name in W.LINEARS:
            qt = tree["blocks"][name]
            for f in ("packed", "scale", "zero", "act_scale"):
                np.testing.assert_array_equal(getattr(qt, f)[layer],
                                              one[name][f])
        np.testing.assert_array_equal(tree["blocks"]["ln1"][layer],
                                      one["ln1"])
    top = W.top_only(SEED, m, w)
    np.testing.assert_array_equal(tree["head"], top["head"])


def test_reference_unpacks_the_programs_layout():
    from repro.core.qtensor import pack
    codes = jax.random.randint(jax.random.PRNGKey(1), (256, 8), 0, 4)
    packed = pack(codes, 2, axis=-2)
    np.testing.assert_array_equal(dense_lm.unpack_codes(packed, 2), codes)


def test_scale_and_zero_are_bf16_values():
    s = tiny.spec()
    m = model_sizes(s["config"])
    leaf = W.one_layer(SEED, 0, m, s["config"]["quant"],
                       s["config"]["weights"])["w_up"]
    for f in ("scale", "zero", "act_scale"):
        x = leaf[f]
        np.testing.assert_array_equal(
            x, x.astype(jnp.bfloat16).astype(jnp.float32))


# -- costs -------------------------------------------------------------------

def test_decode_flops_sum_of_steps():
    m = model_sizes(tiny.spec()["config"])
    plen, budget = 7, 5
    L, P = m["num_hidden_layers"], costs.block_linear_params(m)
    by_step = sum(2 * (L * P + m["hidden_size"] * m["vocab_size"])
                  + 4 * L * 2 * 128 * (plen + j) for j in range(1, budget))
    assert costs.decode_flops(m, plen, budget) == pytest.approx(by_step)


def test_roofline_takes_the_larger_bound():
    calls = [(197e12, 1.0), (1.0, 819e9)]
    assert costs.roofline_seconds(calls, 197e12, 819e9) == pytest.approx(2.0)


# -- trace reduction -----------------------------------------------------------

OPS = [("fusion", 0, 10), ("quant_gemv_op", 5, 20), ("fusion", 40, 50),
       ("quant_gemv_op", 60, 70), ("copy", 95, 130)]
HOST = [("bench.window", 0, 100), ("bench.wave", 1, 99),
        ("PjitFunction(decode_step)", 18, 45), ("device_get", 71, 99)]


def test_busy_is_the_union_of_operations():
    assert trace.union([(s, e) for _, s, e in OPS], 0, 100) == \
        [(0, 20), (40, 50), (60, 70), (95, 100)]
    assert trace.busy_ns(OPS, 0, 100) == 20 + 10 + 10 + 5


def test_gaps_and_their_attribution():
    tr = trace.Trace(device_ops={"/device:TPU:0": OPS}, host=HOST)
    assert tr.window() == (0, 100)
    assert trace.gaps(OPS, 0, 100) == [(20, 40), (50, 60), (70, 95)]
    got = trace.idle_gaps(tr, OPS, 0, 100, n=2)
    assert [n for n, _ in got] == ["device_get", "PjitFunction(decode_step)"]
    assert [s for _, s in got] == pytest.approx([25e-9, 20e-9])
    # a gap no program event overlaps falls to the innermost bench span
    assert trace.host_activity(HOST, 50, 60) == "bench.wave"


def test_names_from_the_chips_trace():
    """Event names as a v5e trace gives them: the HLO instruction's text."""
    gemv = ("%quant_gemv_op.32 = bf16[32,14336]{1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[32,4096]{1,0} %get-tuple-element.592), "
            "custom_call_target=\"tpu_custom_call\"")
    loop = ("%while.1 = (s32[]{:T(128)}, bf16[32,1,4096]) while((s32[], "
            "bf16[32,1,4096]) %tuple.48), condition=%region_3.12")
    assert trace.op_name(gemv) == "quant_gemv_op"
    assert trace.op_name(loop) == "while"
    assert trace._is_container(loop) and not trace._is_container(gemv)
    assert trace.module_name("jit_prefill_step(12073844154304653997)") == \
        "jit_prefill_step"


def test_kernel_time_by_name():
    ev = trace.named(OPS, "quant_gemv_op", 0, 100)
    assert [(s, e) for _, s, e in ev] == [(5, 20), (60, 70)]
    top = trace.top_ops(OPS, 0, 100, n=2)
    assert [n for n, _ in top] == ["copy", "quant_gemv_op"]
    assert [s for _, s in top] == pytest.approx([35e-9, 25e-9])


def test_recorded_trace_loads(tmp_path):
    """A trace recorded here on the CPU: the loader finds the benchmark's
    spans on the host (a CPU trace has no TPU plane)."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_file(str(tmp_path)))
    lo, hi = tr.window()
    assert hi > lo
    assert tr.device_ops == {}
