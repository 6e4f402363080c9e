"""The latent-attention expert family (``models/mla_moe.py``, Moonlight-16B-A3B
at its reduced preset) against the plain float32 reference
(``models/reference.py``), the grouped packed expert kernel against a dense
per-token top-k, and the normal path: ``quantize_model`` -> ``pack_model``
-> ``serve_scheduled``."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _plain_loop import plain_serve
from repro.configs import get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import pack_model, quantize_model
from repro.core.qtensor import QTensor, pack
from repro.core.tesseraq import TesseraQConfig
from repro.launch.scheduler import Request, make_workload, serve_scheduled
from repro.models import get_model
from repro.models import mla_moe as M
from repro.models import reference as R
from repro.models.common import DEFAULT_CTX, PagedCacheStore, take_layer

ARCH = "moonlight-16b-a3b"


def _cfg(dtype):
    return get_reduced_config(ARCH).replace(dtype=dtype)


def _params(cfg, seed=1):
    return get_model(cfg).init_params(jax.random.PRNGKey(seed))


def _serve_logits(cfg, params, tokens):
    """Prefill on all but the last token, then one decode step through the
    cache: (prefill logits at -2, decode logits at -1)."""
    m = get_model(cfg)
    B, S = tokens.shape
    cache = m.init_cache(B, S + 4, dtype=jnp.dtype(cfg.dtype))
    lp, cache = jax.jit(m.prefill)(params, {"tokens": tokens[:, :-1]}, cache)
    ld, _ = jax.jit(m.decode_step)(params, cache, tokens[:, -1],
                                   jnp.full((B,), S - 1, jnp.int32))
    return np.asarray(lp, np.float32), np.asarray(ld, np.float32)


# Served in float32, prefill and decode agree with the float32 reference to
# float32 rounding (a few ulp of logits of size ~5 after three layers: 1e-4
# leaves 20x room).  The same weights served in bf16 miss it by orders of
# magnitude, so the comparison would catch a bf16 path left in the float32
# program.
TOL = 1e-4


def test_prefill_then_decode_match_the_reference():
    cfg = _cfg("float32")
    params = _params(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 20)))
    want = np.asarray(R.mla_moe_logits(params, cfg, tokens))
    lp, ld = _serve_logits(cfg, params, tokens)
    np.testing.assert_allclose(lp, want[:, -2], rtol=0, atol=TOL)
    np.testing.assert_allclose(ld, want[:, -1], rtol=0, atol=TOL)
    # the reference's tolerance is one bf16 compute would fail
    b16 = _cfg("bfloat16")
    lp16, ld16 = _serve_logits(b16, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params), tokens)
    assert np.abs(ld16 - want[:, -1]).max() > 10 * TOL


def test_absorbed_decode_equals_decompressed():
    """One query over a latent cache: the absorbed form (scores against
    the latent through W_UK, values through W_UV) equals attending over
    keys and values decompressed through wkv_b."""
    cfg = _cfg("float32")
    bp = take_layer(_params(cfg)["blocks"], 0)
    rng = np.random.default_rng(3)
    B, S, H = 2, 12, cfg.num_heads
    m = cfg.mla
    latent = jnp.asarray(rng.normal(size=(B, S, m.latent_dim)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(B, 1, H, m.qk_nope_head_dim)),
                         jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(B, 1, H, m.qk_rope_head_dim)),
                       jnp.float32)
    pos = jnp.asarray([S - 1, 5], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = M._attend_absorbed(bp, q_nope, q_pe, latent, pos, None, cfg,
                                 DEFAULT_CTX)
        kv = (latent[..., :m.kv_lora_rank] @ bp["wkv_b"]).reshape(
            B, S, H, m.qk_nope_head_dim + m.v_head_dim)
        k = jnp.concatenate([kv[..., :m.qk_nope_head_dim], jnp.broadcast_to(
            latent[:, :, None, m.kv_lora_rank:],
            (B, S, H, m.qk_rope_head_dim))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)[:, 0]
        s = jnp.einsum("bhd,bshd->bhs", q, k) / np.sqrt(m.qk_head_dim)
        s = jnp.where(jnp.arange(S)[None, None] <= pos[:, None, None], s,
                      -jnp.inf)
        want = jnp.einsum("bhs,bshv->bhv", jax.nn.softmax(s, -1),
                          kv[..., m.qk_nope_head_dim:]).reshape(B, 1, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_router_selection_and_weights():
    """Top-k of sigmoid scores plus the bias, weighted by the chosen
    sigmoids (not the biased scores) normalised to 1, times 2.446."""
    cfg = _cfg("float32")
    rng = np.random.default_rng(5)
    T, d, E = 16, cfg.d_model, cfg.moe.num_experts
    x = rng.normal(size=(T, d)).astype(np.float32)
    router = rng.normal(size=(d, E)).astype(np.float32) / np.sqrt(d)
    # a bias large enough to reorder the choice against the raw scores
    bias = rng.uniform(-0.3, 0.3, size=(E,)).astype(np.float32)
    idx, w = M.route(jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias),
                     cfg)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    k = cfg.moe.top_k
    want_idx = np.argsort(-(s + bias), axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(want_idx, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    want_w = chosen / chosen.sum(1, keepdims=True) * 2.446
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    assert not np.array_equal(np.sort(want_idx, 1),
                              np.sort(np.argsort(-s, 1)[:, :k], 1))


def _experts(rng, E, K, N, bits, group):
    codes = rng.integers(0, 1 << bits, size=(E, K, N))
    scale = rng.uniform(0.5, 1.5, size=(E, K // group, N)) / np.sqrt(K)
    zero = rng.uniform(0, (1 << bits) - 1, size=(E, K // group, N))
    act = rng.uniform(0.5, 2.0, size=(K,))
    return QTensor(packed=pack(jnp.asarray(codes), bits, axis=-2),
                   scale=jnp.asarray(scale, jnp.float32),
                   zero=jnp.asarray(zero, jnp.float32), bits=bits,
                   group_size=group, shape=(K, N),
                   act_scale=jnp.asarray(act, jnp.float32))


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("case", ["random", "one_expert", "empty_experts",
                                  "single_row"])
def test_quant_gmm_matches_dense_topk(bits, case):
    """The grouped kernel (interpret mode) over the sorted, tile-padded
    layout, gathered back per (token, choice), equals each token's rows
    times each chosen expert's dequantized weight.  float32 activations:
    the kernel's plane-order permutation and group-factored affine are
    exact up to float32 summation order (1e-5 relative)."""
    from repro.kernels.ops import qtensor_gmm
    rng = np.random.default_rng(bits)
    E, K, N, k = 8, 256, 256, 2
    T = 1 if case == "single_row" else 24
    w = _experts(rng, E, K, N, bits, 128)
    x = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    if case == "one_expert":
        idx = np.stack([np.full(T, 3), np.full(T, 3)], 1)
    elif case == "empty_experts":
        idx = rng.choice([1, 6], size=(T, k))
    else:
        idx = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    idx = jnp.asarray(idx, jnp.int32)
    lay = M.group_layout(idx, None, E)
    xs = jnp.zeros((lay.rows, K), x.dtype).at[lay.dest].set(
        jnp.repeat(x, k, axis=0), mode="drop")
    ys = qtensor_gmm(xs, w, lay.tile_expert, lay.n_tiles, row_tile=lay.tm)
    got = np.asarray(ys)[np.asarray(lay.dest)].reshape(T, k, N)
    wd = np.asarray(w.dequantize(jnp.float32), np.float64) \
        / np.asarray(w.act_scale, np.float64)[None, :, None]
    want = np.einsum("tk,tjkn->tjn", np.asarray(x, np.float64),
                     wd[np.asarray(idx)])
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    np.testing.assert_array_equal(np.asarray(lay.counts), counts)
    assert int(lay.n_tiles[0]) == int(np.sum(-(-counts // lay.tm)))


def test_inactive_rows_route_nowhere():
    idx = jnp.asarray([[0, 1], [2, 3], [0, 3]], jnp.int32)
    lay = M.group_layout(idx, jnp.asarray([True, False, True]), 4)
    np.testing.assert_array_equal(np.asarray(lay.counts), [2, 1, 0, 1])
    assert np.all(np.asarray(lay.dest)[2:4] == lay.rows)


@functools.lru_cache(maxsize=None)
def _packed():
    """The reduced preset through the TesseraQ walk (a short one) and
    pack_model, at W2 g16 with AWQ init."""
    cfg = get_reduced_config(ARCH)
    params = _params(cfg, seed=0)
    rng = np.random.default_rng(0)
    calib = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                 (4, 16)))}]
    qcfg = QuantConfig(bits=2, group_size=16)
    pq, qmeta, _ = quantize_model(
        cfg, params, calib, qcfg,
        tcfg=TesseraQConfig(par_iterations=2, steps_per_iteration=2))
    return cfg, pack_model(cfg, pq, qmeta, qcfg)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_quantize_pack_serve_alone_parity(backend):
    """The normal path end to end; scheduled tokens are bit-identical to
    serving each request alone (dropless routing keeps rows independent),
    and each decode step's routing counters come back."""
    cfg, packed = _packed()
    assert isinstance(packed["blocks"]["moe"]["w_gate"], QTensor)
    assert isinstance(packed["dense_blocks"]["wkv_b"], QTensor)
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=1,
                         prompt_lens=(4, 8), budgets=(2, 5))
    sched = serve_scheduled(cfg, packed, reqs, slots=2,
                            kernel_backend=backend)
    for q in reqs:
        alone = plain_serve(cfg, packed, q.prompt[None],
                            gen=q.max_new_tokens, max_seq=sched.max_seq,
                            kernel_backend=backend)
        np.testing.assert_array_equal(alone[0],
                                      sched.requests[q.rid]["tokens"])
    n_moe = cfg.num_layers - cfg.moe.dense_layers
    for q in reqs:
        experts = sched.requests[q.rid]["experts"]
        assert experts.shape == (n_moe, len(q.prompt) + q.max_new_tokens - 1,
                                 cfg.moe.top_k)
        assert ((experts >= 0) & (experts < cfg.moe.num_experts)).all()
    touched = sched.step_counters["experts_touched"]
    largest = sched.step_counters["largest_group"]
    assert touched.shape == largest.shape == (sched.steps, n_moe)
    assert (touched >= 1).all() and (touched <= cfg.moe.num_experts).all()
    # at most every live slot's row reaches one expert
    assert (largest <= 2).all() and (largest >= 1).all()


def test_recorded_experts_are_the_tokens_routing():
    """Each request's record holds, position by position, the experts its
    prompt (prefill) and its decoded tokens (decode steps) were routed to:
    in float32 they equal a whole prefill's routing over the same tokens."""
    cfg = _cfg("float32")
    m = get_model(cfg)
    params = _params(cfg, seed=4)
    reqs = make_workload(cfg.vocab_size, n_requests=3, seed=4,
                         prompt_lens=(5, 9), budgets=(3, 6))
    sched = serve_scheduled(cfg, params, reqs, slots=2)
    for q in reqs:
        got = sched.requests[q.rid]["experts"]
        seq = np.concatenate([q.prompt,
                              sched.requests[q.rid]["tokens"][:-1]])
        cache = m.init_cache(1, len(seq), dtype=jnp.float32)
        _, _, rec = m.prefill_record(params, {"tokens": jnp.asarray(
            seq[None])}, cache)
        want = np.asarray(rec["token"]["experts"])[:, 0]
        np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))


def test_paged_chunked_and_tp_are_refused_clearly():
    cfg = get_reduced_config(ARCH)
    model = get_model(cfg)
    assert not model.cache_spec.chunkable and not model.cache_spec.pageable
    with pytest.raises(NotImplementedError, match="pageable"):
        PagedCacheStore(model, slots=2, max_seq=32, page_size=16,
                        num_pages=4)
    from repro.launch.mesh import serve_mesh
    from repro.launch.steps import make_serve_steps
    with pytest.raises(NotImplementedError, match="latent attention"):
        make_serve_steps(cfg, serve_mesh(tp=1), tp_shard=True)
    # a chunked request falls back to whole prefill
    reqs = [Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                    max_new_tokens=3)]
    res = serve_scheduled(cfg, model.init_params(jax.random.PRNGKey(0)),
                          reqs, slots=1, prefill_chunk=4)
    assert res.prefill_chunk == 0
