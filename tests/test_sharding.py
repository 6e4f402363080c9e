"""Sharding-rule unit tests (no multi-device requirement) + subprocess
dry-runs on a small forced-device mesh."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_dryrun(args, timeout=540):
    env = dict(os.environ,
               PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + args,
        capture_output=True, text=True, env=env, timeout=timeout)


# -- pure-logic tests -----------------------------------------------------

def make_test_mesh():
    # reuse the single real device: a (1,1) mesh exercises the code paths
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


# -- mesh helper edge cases ------------------------------------------------

def test_mesh_helpers_on_1device_meshes():
    from repro.launch.mesh import (dp_axes, dp_size, make_data_mesh,
                                   make_mesh, tp_axis)
    m2 = make_mesh((1, 1))
    assert m2.axis_names == ("data", "model")
    assert dp_axes(m2) == ("data",)
    assert dp_size(m2) == 1
    assert tp_axis(m2) == "model"

    m1 = make_data_mesh(1)
    assert m1.axis_names == ("data",)
    assert dp_axes(m1) == ("data",)
    assert dp_size(m1) == 1
    assert tp_axis(m1) is None

    # a 1-axis mesh from the generic constructor defaults to the data axis
    assert make_mesh((1,)).axis_names == ("data",)


def test_make_data_mesh_spans_all_devices():
    from repro.launch.mesh import dp_size, make_data_mesh
    assert dp_size(make_data_mesh()) == len(jax.devices())


def test_compressed_psum_matches_fp32_psum():
    """``compressed_psum`` must run on a pod mesh and reduce within int8
    quantization error of the fp32 psum.  Runs 8-way under the CI
    multidevice job; a 1-device mesh still covers the shard_map path."""
    from repro.launch.mesh import make_mesh
    from repro.optim.compression import compressed_psum
    D = len(jax.devices())
    mesh = make_mesh((D,), ("pod",))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(D * 2, 64)).astype(np.float32) * 3.0
    out = np.asarray(compressed_psum(jax.numpy.asarray(x), mesh))
    assert out.shape == x.shape
    # every pod's block must hold the cross-pod sum of its block-position
    blocks = x.reshape(D, 2, 64)
    want = np.broadcast_to(blocks.sum(0), (D, 2, 64)).reshape(D * 2, 64)
    # int8 wire error: <= half a quantization step per pod summand
    tol = D * np.abs(x).max() / 127.0
    np.testing.assert_allclose(out, want, atol=tol)
    # parity on the single-pod mesh must be exact-ish even at int8
    if D == 1:
        np.testing.assert_allclose(out, x, atol=np.abs(x).max() / 127.0)


def test_make_mesh_axis_name_defaults(monkeypatch):
    """Axis naming for 2- and 3-axis shapes without constructing devices."""
    import repro.launch.mesh as M
    calls = []
    monkeypatch.setattr(M, "_mk", lambda shape, axes: calls.append(
        (shape, axes)))
    M.make_mesh((2, 4))
    M.make_mesh((2, 4, 4))
    assert calls == [((2, 4), ("data", "model")),
                     ((2, 4, 4), ("pod", "data", "model"))]


def test_production_mesh_shapes(monkeypatch):
    """Single-pod vs multi-pod production topologies (the 512-chip mesh
    cannot be constructed on the test host, so record the _mk request)."""
    import repro.launch.mesh as M
    calls = []
    monkeypatch.setattr(M, "_mk", lambda shape, axes: calls.append(
        (shape, axes)))
    M.make_production_mesh()
    M.make_production_mesh(multi_pod=True)
    assert calls == [((16, 16), ("data", "model")),
                     ((2, 16, 16), ("pod", "data", "model"))]


class FakeProductionMesh:
    """Stand-in with the 2-pod 512-chip topology's names/extents — the
    helpers under test only read ``axis_names`` + ``shape``."""
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


def test_dp_helpers_on_multi_pod_mesh():
    """dp_axes/dp_size/tp_axis only read axis_names + shape, so the 2-pod
    512-chip topology is testable with a stand-in."""
    from repro.launch.mesh import dp_axes, dp_size, tp_axis

    assert dp_axes(FakeProductionMesh) == ("pod", "data")
    assert dp_size(FakeProductionMesh) == 32
    assert tp_axis(FakeProductionMesh) == "model"


def test_tp_and_pod_helpers():
    """tp_size / pod_axis / pod_count across mesh shapes, including the
    0/1-safe degenerate cases mirroring dp_size's contract."""
    from repro.launch.mesh import (make_data_mesh, make_mesh, pod_axis,
                                   pod_count, tp_size)
    assert tp_size(None) == 1 and pod_count(None) == 1
    assert pod_axis(None) is None

    m1 = make_data_mesh(1)
    assert tp_size(m1) == 1 and pod_axis(m1) is None and pod_count(m1) == 1

    m2 = make_mesh((1, 1))
    assert tp_size(m2) == 1 and pod_axis(m2) is None

    m3 = make_mesh((1, 1, 1))
    assert pod_axis(m3) == "pod" and pod_count(m3) == 1
    assert tp_size(m3) == 1

    assert tp_size(FakeProductionMesh) == 16
    assert pod_count(FakeProductionMesh) == 2


def test_pod_submeshes_and_memoization():
    """Per-pod submeshes drop the pod axis, keep the rest, and are memoized
    (distinct-but-equal Mesh objects would defeat the jit cache, so every
    resolution of the same pod must hand back the SAME objects)."""
    from repro.launch.mesh import make_data_mesh, make_mesh, pod_submeshes
    m3 = make_mesh((1, 1, 1))
    pods = pod_submeshes(m3)
    assert len(pods) == 1
    assert pods[0].axis_names == ("data", "model")
    assert pods[0].shape == {"data": 1, "model": 1}
    assert pod_submeshes(m3)[0] is pods[0]
    # a mesh without a pod axis is its own (only) submesh
    m1 = make_data_mesh(1)
    assert pod_submeshes(m1) == [m1]


def test_reshard_between_pods_pytrees():
    """The cross-pod seam: pytrees land on the destination mesh under its
    batch spec by default, None leaves pass through, explicit specs are
    honored."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import (batch_spec, make_mesh,
                                   pod_submeshes, reshard_between_pods)
    dst = pod_submeshes(make_mesh((1, 1, 1)))[0]
    x = {"a": np.arange(8, dtype=np.float32).reshape(4, 2), "b": None}
    out = reshard_between_pods(x, dst)
    assert out["b"] is None
    np.testing.assert_array_equal(np.asarray(out["a"]), x["a"])
    assert out["a"].sharding == NamedSharding(dst, batch_spec(dst))
    # explicit replicated spec
    out2 = reshard_between_pods(x["a"], dst, spec=P())
    assert out2.sharding == NamedSharding(dst, P())


def test_validate_single_pod():
    from repro.launch.mesh import make_mesh, validate_single_pod
    validate_single_pod(None, "x")                       # no mesh: fine
    validate_single_pod(make_mesh((1, 1)), "x")          # no pod axis: fine
    validate_single_pod(make_mesh((1, 1, 1)), "x")       # pod extent 1: fine
    with pytest.raises(ValueError) as ei:
        validate_single_pod(FakeProductionMesh, "the scheduler")
    msg = str(ei.value)
    # the message must name the offending axes and point at the remedy
    assert "the scheduler" in msg
    assert "('pod', 'data', 'model')" in msg
    assert "pod_submeshes" in msg


def test_serving_entry_points_reject_multi_pod_mesh():
    """compile_sched_steps / compile_serve_steps fail fast (before any
    tracing) when handed a multi-pod mesh — serving has no cross-pod path;
    each pod gets its own submesh."""
    from repro.configs import get_reduced_config
    from repro.launch.scheduler import compile_sched_steps
    from repro.launch.serve import compile_serve_steps
    cfg = get_reduced_config("tinyllama-1.1b")
    with pytest.raises(ValueError, match="compile_sched_steps"):
        compile_sched_steps(cfg, max_seq=32, mesh=FakeProductionMesh)
    with pytest.raises(ValueError, match="compile_serve_steps"):
        compile_serve_steps(cfg, mesh=FakeProductionMesh)


def test_param_spec_placements_llama3_405b_smoke():
    """The ParamSpec TP contract on the llama3-405b-smoke block shapes:
    out-split leaves (wq/wk/wv/w_gate/w_up) shard the LAST weight dim,
    in-split leaves (wo/w_down) the SECOND-TO-LAST; rounding state follows
    (nu grouped (..., ng, g, out), scale groupvec (..., ng, out)); leaves a
    TP degree does not divide fall back to replicated."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import ParamSpec

    class SmokePodMesh:                       # one pod's ("data","model")
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    ps = ParamSpec.for_mesh(SmokePodMesh)
    assert ps.active and ps.size == 16
    d, ff = 64, 192                           # llama3-405b-smoke dims
    # out-split weight: (in, out) shards out
    assert ps.weight_spec("wq", (d, d)) == P(None, "model")
    assert ps.weight_spec("w_up", (d, ff)) == P(None, "model")
    # in-split weight: (in, out) shards in
    assert ps.weight_spec("wo", (d, d)) == P("model", None)
    assert ps.weight_spec("w_down", (ff, d)) == P("model", None)
    # rounding state: nu (ng, g, out) — out-split shards out, in-split ng
    assert ps.state_spec("wq", "nu", (2, 32, d)) == P(None, None, "model")
    # group vectors (ng, out): out-split shards out, in-split ng
    assert ps.state_spec("wq", "scale", (2, d)) == P(None, "model")
    # act_scale (in,) shards only for in-split leaves
    assert ps.state_spec("wo", "act_scale", (d,)) == P("model")
    assert ps.state_spec("wq", "act_scale", (d,)) == P()
    # in-split state shards the GROUP-count dim — at these smoke shapes
    # (ng = 2 or 6) TP=16 does not divide it, so it falls back replicated
    # rather than wedging the engine ...
    assert ps.state_spec("w_down", "nu", (ff // 32, 32, d)) == P()
    assert ps.state_spec("wo", "scale", (2, d)) == P()
    # ... while a dividing TP degree shards it

    class TP2Mesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    ps2 = ParamSpec.for_mesh(TP2Mesh)
    assert ps2.state_spec("w_down", "nu", (ff // 32, 32, d)) == \
        P("model", None, None)
    assert ps2.state_spec("wo", "scale", (2, d)) == P("model", None)
    # norms / non-rule leaves are replicated
    assert ps.weight_spec("norm_scale", (d,)) == P()


@pytest.mark.slow
def test_production_mesh_multi_pod_512_devices():
    """make_production_mesh(multi_pod=True) under a 512-device forced host
    platform: axis names/extents, the batch spec spanning (pod, data), the
    per-pod submesh split, and the ParamSpec TP placements on the
    llama3-405b-smoke block — the full multi-pod contract, end to end, in
    a subprocess so the device-count flag cannot leak into other tests."""
    prog = r"""
import json
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_reduced_config
from repro.launch.mesh import (batch_spec, dp_size, make_production_mesh,
                               pod_count, pod_submeshes, tp_size)
from repro.launch.sharding import ParamSpec

mesh = make_production_mesh(multi_pod=True)
assert mesh.axis_names == ("pod", "data", "model")
assert dict(mesh.shape) == {"pod": 2, "data": 16, "model": 16}
assert batch_spec(mesh) == P(("pod", "data"))
assert dp_size(mesh) == 32 and tp_size(mesh) == 16 and pod_count(mesh) == 2

pods = pod_submeshes(mesh)
assert len(pods) == 2
seen = set()
for p in pods:
    assert p.axis_names == ("data", "model")
    assert dict(p.shape) == {"data": 16, "model": 16}
    ids = frozenset(d.id for d in p.devices.flat)
    assert len(ids) == 256
    seen |= ids
assert len(seen) == 512                       # disjoint pods cover the mesh

cfg = get_reduced_config("llama3-405b")
assert cfg.name == "llama3-405b-smoke"
ps = ParamSpec.for_mesh(pods[0])
assert ps.active and ps.size == 16
d, ff = cfg.d_model, cfg.d_ff
assert ps.weight_spec("wq", (d, d)) == P(None, "model")
assert ps.weight_spec("w_down", (ff, d)) == P("model", None)
assert ps.state_spec("wq", "nu", (2, d // 2, d)) == P(None, None, "model")
assert ps.state_spec("wo", "act_scale", (d,)) == P("model")
print(json.dumps({"ok": True}))
"""
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=512")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {"ok": True}


def test_split_minibatches_mesh_resident():
    from repro.core.capture import capture_minibatch, split_minibatches
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh()
    mb = capture_minibatch(mesh)
    assert mb >= 4 and mb >= len(jax.devices())
    x = np.arange(6 * 2 * 4, dtype=np.float32).reshape(6, 2, 4)
    parts = split_minibatches(x, 4, mesh)
    assert [p.shape[0] for p in parts] == [4, 2]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p) for p in parts], 0), x)
    # every part lives on the mesh (sharded when divisible, else replicated)
    for p in parts:
        assert set(p.sharding.mesh.devices.flat) == set(mesh.devices.flat)


def test_resolve_spec_divisibility_fallback():
    from repro.launch.sharding import resolve_spec
    mesh = make_test_mesh()
    spec = resolve_spec(mesh, ("batch", "tensor"), (8, 16))
    # 1-sized axes shard trivially
    assert len(spec) == 2


def test_param_shardings_structure():
    from repro.configs import get_reduced_config
    from repro.launch.sharding import param_shardings
    from repro.models import get_model
    mesh = make_test_mesh()
    for arch in ("tinyllama-1.1b", "qwen3-moe-30b-a3b", "rwkv6-3b",
                 "zamba2-1.2b", "whisper-small"):
        cfg = get_reduced_config(arch)
        m = get_model(cfg)
        ps = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
        specs = param_shardings(mesh, ps, cfg)
        # structurally identical trees
        assert (jax.tree_util.tree_structure(ps)
                == jax.tree_util.tree_structure(specs))


def test_qtensor_sharding_specs():
    from repro.configs import get_reduced_config
    from repro.configs.base import QuantConfig
    from repro.launch.sharding import param_shardings
    from repro.launch.steps import quantize_param_struct
    from repro.models import get_model
    mesh = make_test_mesh()
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    ps = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
    qs = quantize_param_struct(ps, cfg, QuantConfig(bits=4, group_size=32))
    specs = param_shardings(mesh, qs, cfg)
    assert (jax.tree_util.tree_structure(qs)
            == jax.tree_util.tree_structure(specs))


def test_collective_parser():
    from repro.launch.hlo_stats import collective_bytes
    hlo = """
      %ag = bf16[128,256]{1,0} all-gather(bf16[8,256]{1,0} %x), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
      %ar = f32[64]{0} all-reduce(f32[64]{0} %y), replica_groups=[4,2]<=[8]
      %cp = f32[32]{0} collective-permute(f32[32]{0} %z), source_target_pairs={{0,1}}
    """
    out = collective_bytes(hlo, default_group=8)
    assert out["n_ops"] == 3
    ag = 128 * 256 * 2 * (15 / 16)
    ar = 64 * 4 * 2 * (1 / 2)
    cp = 32 * 4
    assert out["per_kind"]["all-gather"] == pytest.approx(ag)
    assert out["per_kind"]["all-reduce"] == pytest.approx(ar)
    assert out["per_kind"]["collective-permute"] == pytest.approx(cp)


# -- subprocess dry-runs on a forced 8-device host platform ---------------

@pytest.mark.slow
def test_dryrun_train_small_mesh(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun(["--arch", "smollm-135m", "--shape", "train_4k",
                    "--mesh", "2,4", "--no-block-correction",
                    "--out", str(out)])
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(out.read_text())
    assert res["status"] == "ok"
    assert res["roofline"]["flops"] > 0


@pytest.mark.slow
def test_dryrun_quantized_decode_small_mesh(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun(["--arch", "tinyllama-1.1b", "--shape", "decode_32k",
                    "--mesh", "2,4", "--quant", "W2A16g128",
                    "--no-block-correction", "--out", str(out)])
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(out.read_text())
    assert res["status"] == "ok"
    # packed weights must shrink the argument bytes vs fp16
    assert res["memory"]["argument_bytes"] > 0


@pytest.mark.slow
def test_dryrun_skip_rule(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun(["--arch", "tinyllama-1.1b", "--shape", "long_500k",
                    "--mesh", "2,4", "--out", str(out)])
    assert r.returncode == 0
    res = json.loads(out.read_text())
    assert res["status"] == "skipped" and "attn" in res["why"]
