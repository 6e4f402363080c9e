"""Tensor-parallel serving contract (``launch.sharding.ServeSpec``).

Three layers of pins:

  * **TP=1-on-mesh bit-identity** (runs on any device count): routing a
    family's packed serve steps through ``serve_mesh(tp=1)`` +
    ``tp_shard=True`` must reproduce the no-mesh path's tokens AND logits
    bit-for-bit — the shard_map wrapper at degree 1 is an identity, for
    every family, both kernel backends and both cache stores.
  * **TP>1 parity** (needs >= 4 devices, the CI multidevice leg): tokens
    match the no-mesh path exactly; logits match within the documented
    psum tolerance (the in-channel reduction is the one reassociation
    seam).  Covers the plain loop and the scheduler under dense,
    paged, and chunked-prefill stores — all transfer-guard-clean via the
    explicit ``ServeSpec.place_params``/``place_cache`` placement.
  * **serve_plan pins** (pure shape logic, no devices): the per-leaf
    feasibility rules — out-split needs ``N % tp``, in-split needs whole
    quant groups (``ng % tp``) AND whole packed container rows
    (``(K // ppb) % tp``), group atomicity pushes a whole attention/FFN
    group back to replicated when any member fails — plus stacked-layer
    containers and the per-shard ``QTensor.memory_bytes`` accounting.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _plain_loop import plain_serve
from repro.configs import get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import pack_model, quantize_model
from repro.core.qtensor import PACK_FACTOR, QTensor
from repro.launch.mesh import serve_mesh
from repro.launch.scheduler import Request, serve_scheduled
from repro.launch.sharding import (ServeSpec, serve_param_specs, serve_plan)
from repro.models import get_model

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="TP>1 parity needs >= 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# one arch per family (vlm/hybrid wrap dense; moe/encdec/rwkv/ssm distinct)
FAMILY_ARCHS = ["llama2-7b", "qwen3-moe-30b-a3b", "whisper-small",
                "rwkv6-3b", "zamba2-1.2b", "paligemma-3b"]


def _calib(cfg):
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))}
    if cfg.family == "encdec":
        b["frames"] = jnp.asarray(
            rng.standard_normal((2, cfg.frontend_len or 16, cfg.d_model)),
            jnp.float32)
    if cfg.family == "vlm":
        b["patches"] = jnp.asarray(
            rng.standard_normal((2, cfg.num_patches, cfg.d_model)),
            jnp.float32)
    return [b]


@functools.lru_cache(maxsize=None)
def _packed(arch):
    """Reduced f32 config + W4g16 RTN-packed params (f32 so the TP>1
    logits tolerance accounts only for psum reassociation, not bf16)."""
    cfg = get_reduced_config(arch).replace(dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    qcfg = QuantConfig(bits=4, group_size=16)
    pq, qmeta, _ = quantize_model(cfg, params, _calib(cfg), qcfg,
                                  method="none", init="rtn")
    return cfg, model, pack_model(cfg, pq, qmeta, qcfg)


def _run_family(cfg, params, mesh, tp_shard, *, backend="xla",
                B=2, S=8, gen=3):
    """The plain prefill+decode loop over the serve steps; returns
    (tokens (B, gen), logits (B, gen, V)) as host arrays."""
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = rng.standard_normal(
            (B, cfg.frontend_len or S, cfg.d_model)).astype(np.float32)
    if cfg.family == "vlm":
        extras["patches"] = rng.standard_normal(
            (B, cfg.num_patches, cfg.d_model)).astype(np.float32)
    return plain_serve(cfg, params, prompts, gen=gen, extras=extras,
                       kernel_backend=backend, mesh=mesh, tp_shard=tp_shard,
                       logits=True)


# -- TP=1 on a mesh is the identity ------------------------------------------

@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_tp1_mesh_bit_identity(arch):
    cfg, model, packed = _packed(arch)
    t0, l0 = _run_family(cfg, packed, None, False)
    t1, l1 = _run_family(cfg, packed, serve_mesh(tp=1), True)
    assert np.array_equal(t0, t1)
    assert np.array_equal(l0, l1)


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_tp1_mesh_bit_identity_pallas(arch):
    cfg, model, packed = _packed(arch)
    t0, l0 = _run_family(cfg, packed, None, False, backend="pallas")
    t1, l1 = _run_family(cfg, packed, serve_mesh(tp=1), True,
                         backend="pallas")
    assert np.array_equal(t0, t1)
    assert np.array_equal(l0, l1)


def _sched_requests(cfg, n=4):
    rng = np.random.RandomState(0)
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size,
                                       size=(8 + 2 * i,)).astype(np.int32),
                    max_new_tokens=4, arrival=i) for i in range(n)]


@pytest.mark.parametrize("store,kw", [
    ("dense", {}),
    ("paged", {"store": "paged", "page_size": 16}),
])
def test_tp1_mesh_sched_bit_identity(store, kw):
    cfg, model, packed = _packed("llama2-7b")
    reqs = _sched_requests(cfg)

    def run(**extra):
        return serve_scheduled(cfg, packed, reqs, slots=2, max_seq=32,
                               collect_logits=True, **kw, **extra)

    ref = run()
    got = run(mesh=serve_mesh(tp=1), tp_shard=True)
    for r in reqs:
        assert np.array_equal(ref.requests[r.rid]["tokens"],
                              got.requests[r.rid]["tokens"])
        assert np.array_equal(ref.requests[r.rid]["logits"],
                              got.requests[r.rid]["logits"])


# -- TP>1: tokens exact, logits within the psum tolerance --------------------

@needs4
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_tp4_serve_parity(arch):
    cfg, model, packed = _packed(arch)
    t0, l0 = _run_family(cfg, packed, None, False)
    t4, l4 = _run_family(cfg, packed, serve_mesh(tp=4), True)
    assert np.array_equal(t0, t4)
    np.testing.assert_allclose(l0, l4, rtol=5e-3, atol=5e-3)


@needs4
@pytest.mark.parametrize("store,kw", [
    ("dense", {}),
    ("paged", {"store": "paged", "page_size": 16}),
    ("paged_chunked", {"store": "paged", "page_size": 16,
                       "prefill_chunk": 8}),
])
def test_tp4_sched_token_parity(store, kw):
    cfg, model, packed = _packed("llama2-7b")
    reqs = _sched_requests(cfg)

    def run(**extra):
        return serve_scheduled(cfg, packed, reqs, slots=2, max_seq=32,
                               **kw, **extra)

    ref = run()
    got = run(mesh=serve_mesh(tp=4), tp_shard=True)
    for r in reqs:
        assert np.array_equal(ref.requests[r.rid]["tokens"],
                              got.requests[r.rid]["tokens"])


@needs4
def test_tp4_sched_transfer_guard_clean():
    """The scheduler TP path dispatches with ZERO implicit transfers: the
    explicit ServeSpec placement commits params/cache/host pushes to their
    contract shardings, so the whole loop runs under transfer_guard."""
    cfg, model, packed = _packed("llama2-7b")
    reqs = _sched_requests(cfg)
    mesh = serve_mesh(tp=4)
    kw = dict(slots=2, max_seq=32, mesh=mesh, tp_shard=True)
    serve_scheduled(cfg, packed, reqs, **kw)           # warm compile
    with jax.transfer_guard("disallow"):
        serve_scheduled(cfg, packed, reqs, **kw)


# -- serve_plan feasibility pins ---------------------------------------------

def _qt(K, N, bits, g, lead=()):
    ppb = PACK_FACTOR[bits]
    return QTensor(packed=np.zeros(lead + (K // ppb, N), np.uint8),
                   scale=np.ones(lead + (K // g, N), np.float32),
                   zero=np.zeros(lead + (K // g, N), np.float32),
                   bits=bits, group_size=g, shape=(K, N))


def test_serve_plan_tp1_shards_everything():
    cfg, _, packed = _packed("llama2-7b")
    plan = serve_plan(cfg, packed, 1)
    assert set(plan) == {"wq", "wk", "wv", "wo",
                         "w_gate", "w_up", "w_down"}


def test_serve_plan_ffn_group_fallback():
    """llama2-7b reduced at W4g16: d_ff=176 -> ng=11 on w_down, so the
    whole FFN group (gate/up/down — atomicity) falls back to replicated
    at tp=4 while attention still shards."""
    cfg, _, packed = _packed("llama2-7b")
    plan = serve_plan(cfg, packed, 4)
    assert plan == {"wq": "out", "wk": "out", "wv": "out", "wo": "in"}


def test_serve_plan_w2_grouped_ng_fallback():
    """W2 grouped codes whose group-count dim does not divide tp: the
    in-split member (wo: K=48, g=16 -> ng=3) fails ng % 4, so the WHOLE
    attention group replicates — even though the packed container rows
    (K//ppb = 12) would divide."""
    cfg = get_reduced_config("llama2-7b")
    params = {"wq": _qt(64, 8, 2, 16), "wk": _qt(64, 8, 2, 16),
              "wv": _qt(64, 8, 2, 16), "wo": _qt(48, 64, 2, 16)}
    assert serve_plan(cfg, params, 4) == {}
    # control: ng divisible -> the same group shards
    params["wo"] = _qt(64, 64, 2, 16)
    assert serve_plan(cfg, params, 4) == {
        "wq": "out", "wk": "out", "wv": "out", "wo": "in"}


def test_serve_plan_w3_container_row_fallback():
    """W3 packs two values per container row (ppb=2): wo with K=6, g=3
    has ng=2 (divides tp=2) but K//ppb=3 rows — a shard boundary would
    split a container row, so the group falls back to replicated."""
    cfg = get_reduced_config("llama2-7b")
    params = {"wq": _qt(64, 8, 3, 16), "wk": _qt(64, 8, 3, 16),
              "wv": _qt(64, 8, 3, 16), "wo": _qt(6, 64, 3, 3)}
    assert serve_plan(cfg, params, 2) == {}


def test_serve_plan_head_count_gates_attn_group():
    """Attention-group atomicity includes the head counts: shapes that
    divide tp still replicate when num_heads does not (the forward
    reshapes by heads)."""
    cfg = get_reduced_config("llama2-7b")
    params = {"wq": _qt(64, 64, 4, 16), "wk": _qt(64, 64, 4, 16),
              "wv": _qt(64, 64, 4, 16), "wo": _qt(64, 64, 4, 16)}
    assert serve_plan(cfg, params, 4) != {}
    cfg3 = cfg.replace(num_heads=3, num_kv_heads=3)
    assert serve_plan(cfg3, params, 4) == {}


def test_serve_plan_stacked_containers():
    """Stacked-layer QTensor containers (leading scan dim on the arrays,
    2-D logical shape) shard exactly like flat ones, and the spec tree
    places the TP axis on the correct TRAILING dim of each child."""
    cfg = get_reduced_config("llama2-7b")
    L = 2
    params = {"wq": _qt(64, 64, 4, 16, lead=(L,)),
              "wk": _qt(64, 64, 4, 16, lead=(L,)),
              "wv": _qt(64, 64, 4, 16, lead=(L,)),
              "wo": _qt(64, 64, 4, 16, lead=(L,))}
    plan = serve_plan(cfg, params, 4)
    assert plan == {"wq": "out", "wk": "out", "wv": "out", "wo": "in"}
    specs = serve_param_specs(params, plan, "model")
    from jax.sharding import PartitionSpec as P
    assert specs["wq"].packed == P(None, None, "model")     # out: dim -1
    assert specs["wq"].scale == P(None, None, "model")
    assert specs["wo"].packed == P(None, "model", None)     # in: dim -2
    assert specs["wo"].scale == P(None, "model", None)


# -- per-shard memory accounting ---------------------------------------------

def _leaf(tree, name):
    if isinstance(tree, dict):
        for k, v in tree.items():
            if k == name and isinstance(v, QTensor):
                return v
            got = _leaf(v, name)
            if got is not None:
                return got
    return None


@needs4
def test_memory_bytes_is_per_shard_under_tp():
    """QTensor.memory_bytes reports the ADDRESSABLE (per-device) bytes:
    an out-split leaf placed over tp=4 reports a quarter of its global
    container+metadata bytes; a replicated-fallback leaf still reports
    the full amount."""
    cfg, _, packed = _packed("llama2-7b")
    spec = ServeSpec.for_mesh(serve_mesh(tp=4), cfg)
    plan = spec.plan(packed)
    assert plan.get("wq") == "out" and "w_up" not in plan
    placed = spec.place_params(packed, plan)
    g_wq, l_wq = _leaf(packed, "wq"), _leaf(placed, "wq")
    assert l_wq.memory_bytes() * 4 == g_wq.memory_bytes()
    g_up, l_up = _leaf(packed, "w_up"), _leaf(placed, "w_up")
    assert l_up.memory_bytes() == g_up.memory_bytes()
