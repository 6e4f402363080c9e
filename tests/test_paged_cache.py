"""Paged KV cache tests: allocator edge cases (exhaustion, oversized
requests), copy-on-write prefix sharing, chunked-prefill page boundaries,
and the headline contract — dense-vs-paged bit-identity per family on both
kernel backends (the dense store is the parity anchor)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.launch.scheduler import (Request, compile_sched_steps,
                                    make_workload, serve_scheduled)
from repro.models import get_model
from repro.models.common import PagedCacheStore


@pytest.fixture(scope="module")
def dense():
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    return cfg, m, params


def _tokens_equal(a, b, reqs):
    for q in reqs:
        np.testing.assert_array_equal(
            a.requests[q.rid]["tokens"], b.requests[q.rid]["tokens"],
            err_msg=f"rid {q.rid} diverged")


# -- allocator edge cases ----------------------------------------------------

def test_pool_exhaustion_graceful_refusal(dense):
    """A pool too small for two concurrent requests refuses (doesn't crash)
    admission; the queued request completes once pages free up, with the
    same tokens the dense store produces."""
    cfg, m, params = dense
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (9,)).astype(
                        np.int32),
                    max_new_tokens=6, arrival=0) for i in range(3)]
    # each request: 15 positions -> 2 pages of 8; pool of 3 fits one at a time
    paged = serve_scheduled(cfg, params, reqs, slots=3, max_seq=32,
                            store="paged", page_size=8, num_pages=3)
    ref = serve_scheduled(cfg, params, reqs, slots=3, max_seq=32)
    _tokens_equal(paged, ref, reqs)
    assert paged.cache_stats["refused_admissions"] >= 1
    assert paged.cache_stats["pages_in_use"] == 0           # all released
    assert paged.cache_stats["peak_pages_in_use"] <= 3


def test_request_longer_than_pool_raises(dense):
    """A request that could NEVER fit the pool fails fast with ValueError
    instead of deadlocking the queue."""
    cfg, m, params = dense
    req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                  max_new_tokens=8, arrival=0)       # 17 positions -> 3 pages
    with pytest.raises(ValueError, match="never be admitted"):
        serve_scheduled(cfg, params, [req], slots=1, max_seq=32,
                        store="paged", page_size=8, num_pages=2)


def test_store_rejects_misaligned_width(dense):
    cfg, m, _ = dense
    with pytest.raises(ValueError, match="multiple of page_size"):
        PagedCacheStore(m, slots=1, max_seq=30, page_size=8, num_pages=4)


# -- copy-on-write prefix sharing -------------------------------------------

def test_prefix_share_then_diverge(dense):
    """Two prompts with a common 24-token prefix: the sharer reuses the
    full prefix pages (hits > 0) and both requests' outputs are identical
    to a run with sharing disabled."""
    cfg, m, params = dense
    common = np.arange(100, 124, dtype=np.int32)
    reqs = [Request(rid=0, prompt=common.copy(), max_new_tokens=4,
                    arrival=0),
            Request(rid=1,
                    prompt=np.concatenate([common, [7, 9]]).astype(np.int32),
                    max_new_tokens=4, arrival=2)]
    kw = dict(slots=2, max_seq=32, store="paged", page_size=8,
              prefill_chunk=8)
    shared = serve_scheduled(cfg, params, reqs, share_prefix=True, **kw)
    plain = serve_scheduled(cfg, params, reqs, **kw)
    _tokens_equal(shared, plain, reqs)
    assert shared.cache_stats["shared_page_hits"] > 0
    assert plain.cache_stats["shared_page_hits"] == 0
    assert shared.cache_stats["pages_in_use"] == 0


def test_shared_pages_refcounted(dense):
    """Direct allocator check: a shared page is freed only when the LAST
    holder releases it, and the prefix map forgets it afterwards."""
    cfg, m, _ = dense
    store = PagedCacheStore(m, slots=2, max_seq=32, page_size=8,
                            num_pages=6)
    prompt = np.arange(17, dtype=np.int32)
    p0 = store.try_admit(0, 20, prompt=prompt, share=True)
    assert p0 is not None and p0.shared_tokens == 0
    store.register_prefix(0, prompt)
    p1 = store.try_admit(1, 20, prompt=prompt.copy(), share=True)
    assert p1.shared_tokens == 16                    # 2 full prefix pages
    assert p1.pages[:2] == p0.pages[:2]
    store.release(0)
    assert store.stats()["pages_in_use"] == 3        # sharer still holds 3
    store.release(1)
    assert store.stats()["pages_in_use"] == 0
    # prefix map emptied: a fresh admit shares nothing
    p2 = store.try_admit(0, 20, prompt=prompt, share=True)
    assert p2.shared_tokens == 0


# -- chunked prefill ---------------------------------------------------------

def test_chunk_boundary_exactly_at_page_size(dense):
    """Prompt length a multiple of page_size with chunk == page_size: every
    chunk ends exactly on a page boundary.  Dense and paged stores at the
    SAME chunk schedule stay bit-identical."""
    cfg, m, params = dense
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (16,)).astype(
                        np.int32),
                    max_new_tokens=4, arrival=i) for i in range(2)]
    kw = dict(slots=2, max_seq=32, prefill_chunk=8)
    dense_run = serve_scheduled(cfg, params, reqs, **kw)
    paged_run = serve_scheduled(cfg, params, reqs, store="paged",
                                page_size=8, **kw)
    _tokens_equal(paged_run, dense_run, reqs)


def test_chunked_vs_whole_prefill_agree(dense):
    """Chunked prefill reproduces whole prefill's generations (allclose in
    logits -> same argmax stream on this model)."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=13,
                         prompt_lens=(5, 12), budgets=(2, 5), mean_gap=1.0)
    whole = serve_scheduled(cfg, params, reqs, slots=2, max_seq=32)
    chunked = serve_scheduled(cfg, params, reqs, slots=2, max_seq=32,
                              prefill_chunk=4)
    _tokens_equal(chunked, whole, reqs)
    assert chunked.prefill_chunk == 4


# -- dense vs paged bit-identity, per family, both backends ------------------

FAMILY_ARCHS = ["tinyllama-1.1b", "zamba2-1.2b", "rwkv6-3b",
                "whisper-small", "paligemma-3b"]


def _family_requests(cfg, rng, n=3):
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(4, 8))
        extras = None
        if cfg.family == "encdec":
            extras = {"frames": rng.normal(
                size=(cfg.frontend_len, cfg.d_model)).astype(np.float32)}
        elif cfg.family == "vlm":
            extras = {"patches": rng.normal(
                size=(cfg.num_patches, cfg.d_model)).astype(np.float32)}
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 4)), arrival=rid,
            extras=extras))
    return reqs


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_dense_vs_paged_identity(arch, backend):
    """THE paging contract: for every family and both kernel backends the
    paged store emits bit-identical per-request tokens to the dense store.
    On pallas the dense side pins decode_attn_chunk == page_size so both
    kernels walk the same chunk grid (identical reduction order)."""
    cfg = get_reduced_config(arch)
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(5))
    reqs = _family_requests(cfg, np.random.default_rng(5))
    psz = 8
    extra = cfg.num_patches if cfg.family == "vlm" else 0
    max_seq = -(-(extra + 8 + 4) // psz) * psz
    d_steps = compile_sched_steps(cfg, max_seq=max_seq,
                                  kernel_backend=backend,
                                  decode_attn_chunk=psz)
    p_steps = compile_sched_steps(cfg, max_seq=max_seq,
                                  kernel_backend=backend, page_size=psz)
    dense_run = serve_scheduled(cfg, params, reqs, slots=2, max_seq=max_seq,
                                kernel_backend=backend, compiled=d_steps)
    paged_run = serve_scheduled(cfg, params, reqs, slots=2, max_seq=max_seq,
                                kernel_backend=backend, compiled=p_steps,
                                store="paged", page_size=psz)
    _tokens_equal(paged_run, dense_run, reqs)
    assert paged_run.cache_stats["store"] == "paged"
    assert paged_run.cache_stats["pages_in_use"] == 0


def test_dense_vs_paged_logits_identity(dense):
    """Stronger than token equality: the full decode logits streams match
    bit-for-bit on the anchor family."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=17,
                         prompt_lens=(4, 10), budgets=(3, 5), mean_gap=1.0)
    a = serve_scheduled(cfg, params, reqs, slots=2, max_seq=32,
                        collect_logits=True)
    b = serve_scheduled(cfg, params, reqs, slots=2, max_seq=32,
                        collect_logits=True, store="paged", page_size=8)
    for q in reqs:
        np.testing.assert_array_equal(a.requests[q.rid]["logits"],
                                      b.requests[q.rid]["logits"])


# -- memory ------------------------------------------------------------------

def test_paged_pool_bytes_below_dense(dense):
    """The paged store's reason to exist: on a LONG-TAILED plan, a dense
    store reserves a full-width lane per slot, while a page pool sized to
    hold the longest request with room to spare serves the same requests
    with the same tokens in under half the dense store's bytes (pool plus
    page table, as allocated)."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=8, seed=19,
                         prompt_lens=(4, 6), budgets=(2, 4), long_frac=0.25,
                         long_prompt_lens=(40, 44), long_budgets=(8, 12))
    assert any(len(r.prompt) >= 40 for r in reqs)          # genuinely long
    assert sum(len(r.prompt) < 40 for r in reqs) > len(reqs) // 2
    kw = dict(slots=4, max_seq=56)
    dense_run = serve_scheduled(cfg, params, reqs, **kw)
    paged_run = serve_scheduled(cfg, params, reqs, store="paged",
                                page_size=8, num_pages=12, **kw)
    _tokens_equal(paged_run, dense_run, reqs)
    assert paged_run.cache_stats["pages_in_use"] == 0
    assert (paged_run.cache_stats["cache_bytes"]
            <= 0.5 * dense_run.cache_stats["cache_bytes"])
