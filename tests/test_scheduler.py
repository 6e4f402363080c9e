"""Continuous-batching scheduler tests: slot cache plumbing, completion
masking, admission determinism, compile-once decode, family coverage, and
packed-backend parity (the serve-path acceptance gates in miniature)."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _plain_loop import plain_serve
from repro.configs import get_reduced_config
from repro.launch import scheduler as S
from repro.launch.scheduler import (Request, compile_sched_steps,
                                    make_workload, serve_scheduled)
from repro.models import get_model
from repro.models.common import read_slot, write_slot


@pytest.fixture(scope="module")
def dense():
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    return cfg, m, params


def assert_alone_parity(cfg, params, reqs, sched, **serve_kw):
    """Every scheduled request's tokens == serving it alone through the
    plain loop (same width)."""
    for q in reqs:
        alone = plain_serve(cfg, params, q.prompt[None],
                            gen=q.max_new_tokens, max_seq=sched.max_seq,
                            **serve_kw)
        np.testing.assert_array_equal(
            alone[0], sched.requests[q.rid]["tokens"],
            err_msg=f"rid {q.rid} diverged from standalone serving")


# -- slot cache plumbing (models/common.py) ---------------------------------

def test_write_read_slot_roundtrip(dense):
    cfg, m, _ = dense
    cache = m.init_cache(4, 12)
    one = jax.tree_util.tree_map(
        lambda leaf: jnp.ones(leaf.shape[:1] + (1,) + leaf.shape[2:],
                              leaf.dtype),
        m.init_cache(1, 12))
    out = write_slot(cache, one, 2)
    back = read_slot(out, 2)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(one), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the other slots stay untouched (zeros)
    for s in (0, 1, 3):
        for leaf in jax.tree_util.tree_leaves(read_slot(out, s)):
            assert not np.asarray(leaf).any()


# -- completion masking ------------------------------------------------------

def test_finished_request_is_frozen(dense):
    """A short request sharing slots with a long one gets EXACTLY its token
    budget, matches standalone serving, and its stream is unchanged when
    the engine keeps stepping for an even longer neighbor."""
    cfg, m, params = dense
    rng = np.random.default_rng(0)
    short = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, (6,))
                    .astype(np.int32), max_new_tokens=2)
    long_ = Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, (9,))
                    .astype(np.int32), max_new_tokens=9)
    sched = serve_scheduled(cfg, params, [short, long_], slots=2, max_seq=24)
    assert sched.requests[0]["tokens"].shape == (2,)
    assert sched.requests[1]["tokens"].shape == (9,)
    assert_alone_parity(cfg, params, [short, long_], sched)
    # stretch the neighbor: the finished request's stream must not move
    longer = dataclasses.replace(long_, max_new_tokens=14)
    sched2 = serve_scheduled(cfg, params, [short, longer], slots=2,
                             max_seq=24)
    np.testing.assert_array_equal(sched.requests[0]["tokens"],
                                  sched2.requests[0]["tokens"])


# -- admission ---------------------------------------------------------------

def test_admission_determinism_and_alone_parity(dense):
    """More requests than slots with staggered arrivals: the same seeded
    plan reproduces the same tokens, and every request matches serving it
    alone — admission into freed slots mid-decode is invisible to the
    requests already decoding."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=6, seed=3,
                         prompt_lens=(4, 10), budgets=(2, 8))
    assert len({len(r.prompt) for r in reqs}) > 1          # genuinely ragged
    assert len({r.arrival for r in reqs}) > 1              # staggered
    s1 = serve_scheduled(cfg, params, reqs, slots=2)
    s2 = serve_scheduled(cfg, params, reqs, slots=2)
    for q in reqs:
        np.testing.assert_array_equal(s1.requests[q.rid]["tokens"],
                                      s2.requests[q.rid]["tokens"])
        assert s1.requests[q.rid]["admit_step"] == \
            s2.requests[q.rid]["admit_step"]
    assert_alone_parity(cfg, params, reqs, s1)
    # queueing really happened: someone was admitted after its arrival
    waits = [s1.requests[q.rid]["admit_step"] - q.arrival for q in reqs]
    assert max(waits) > 0
    assert s1.latency_steps["p99"] >= s1.latency_steps["p50"]


def test_uniform_workload_matches_lockstep_loop(dense):
    """Parity anchor: on a UNIFORM workload (same prompt len, same budget,
    all arrive at once, slots == requests) the scheduler reproduces the
    plain lock-step loop over a B-row prefill token-for-token."""
    cfg, m, params = dense
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    gen = 4
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gen)
            for i in range(3)]
    sched = serve_scheduled(cfg, params, reqs, slots=3)
    lock = plain_serve(cfg, params, prompts, gen=gen, max_seq=sched.max_seq)
    np.testing.assert_array_equal(lock, sched.token_matrix())


# -- compile-once decode -----------------------------------------------------

def test_decode_compiles_once_across_occupancy(dense):
    """Occupancy is a traced mask: admissions, completions, and partially
    empty steps must all reuse ONE decode executable."""
    cfg, _, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=5, seed=0,
                         prompt_lens=(4, 8), budgets=(1, 6), mean_gap=2.0)
    comp = compile_sched_steps(cfg, max_seq=14)
    sched = serve_scheduled(cfg, params, reqs, slots=2, max_seq=14,
                            compiled=comp)
    assert sched.steps > 0
    assert comp.decode._cache_size() == 1
    # a second workload at the same config keeps reusing it
    more = make_workload(cfg.vocab_size, n_requests=3, seed=9,
                         prompt_lens=(4, 8), budgets=(2, 6))
    serve_scheduled(cfg, params, more, slots=2, max_seq=14, compiled=comp)
    assert comp.decode._cache_size() == 1


# -- validation --------------------------------------------------------------

def test_scheduler_validates_inputs(dense):
    cfg, _, params = dense
    r = Request(rid=0, prompt=np.zeros((4,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="at least one slot"):
        serve_scheduled(cfg, params, [r], slots=0)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        serve_scheduled(cfg, params, [r], slots=1, max_seq=6)
    bad = Request(rid=1, prompt=np.zeros((4,), np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        serve_scheduled(cfg, params, [bad], slots=1)


# -- every family runs the scheduler ----------------------------------------

@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-1.2b"])
def test_scheduler_family_alone_parity(arch):
    """Row-independent families (attention, recurrence, hybrid): scheduled
    tokens are bit-identical to serving each request alone."""
    cfg = get_reduced_config(arch)
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(1))
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=1,
                         prompt_lens=(4, 8), budgets=(2, 5))
    sched = serve_scheduled(cfg, params, reqs, slots=2)
    assert_alone_parity(cfg, params, reqs, sched)


def test_scheduler_moe_deterministic():
    """MoE capacity dispatch couples batch rows by construction, so MoE
    gets a determinism contract rather than alone-parity."""
    cfg = get_reduced_config("qwen3-moe-30b-a3b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(2))
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=2,
                         prompt_lens=(4, 8), budgets=(2, 5))
    s1 = serve_scheduled(cfg, params, reqs, slots=2)
    s2 = serve_scheduled(cfg, params, reqs, slots=2)
    for q in reqs:
        assert s1.requests[q.rid]["tokens"].shape == (q.max_new_tokens,)
        np.testing.assert_array_equal(s1.requests[q.rid]["tokens"],
                                      s2.requests[q.rid]["tokens"])


def test_scheduler_vlm_extras():
    """Multimodal prefill inputs ride along per request via ``extras``."""
    cfg = get_reduced_config("paligemma-3b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    reqs = []
    for rid in range(3):
        plen = int(rng.integers(4, 8))
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 5)),
            extras={"patches": rng.normal(size=(
                cfg.num_patches, cfg.d_model)).astype(np.float32)}))
    # patches occupy cache positions too: width must cover them
    max_seq = max(cfg.num_patches + len(r.prompt) + r.max_new_tokens
                  for r in reqs)
    s1 = serve_scheduled(cfg, params, reqs, slots=2, max_seq=max_seq)
    s2 = serve_scheduled(cfg, params, reqs, slots=2, max_seq=max_seq)
    for q in reqs:
        assert s1.requests[q.rid]["tokens"].shape == (q.max_new_tokens,)
        np.testing.assert_array_equal(s1.requests[q.rid]["tokens"],
                                      s2.requests[q.rid]["tokens"])


# -- packed QTensor backends -------------------------------------------------

def test_scheduler_packed_backend_alone_parity(dense):
    """The acceptance gate in miniature: scheduled outputs bit-identical to
    serving alone on BOTH kernel backends, on packed W4 weights."""
    from repro.configs.base import QuantConfig
    from repro.core import pack_model, quantize_model
    from repro.data.pipeline import DataConfig, calibration_batches
    cfg, m, params = dense
    qcfg = QuantConfig(bits=4, group_size=32)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=10, global_batch=2,
                    seed=0)
    calib = [{"tokens": jnp.asarray(b["tokens"][:, :-1])}
             for b in calibration_batches(dc, 1, 2)]
    pq, qmeta, _ = quantize_model(cfg, params, calib, qcfg, method="none",
                                  init="rtn")
    packed = pack_model(cfg, pq, qmeta, qcfg)
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=4,
                         prompt_lens=(4, 9), budgets=(2, 6))
    for backend in ("xla", "pallas"):
        sched = serve_scheduled(cfg, packed, reqs, slots=2,
                                kernel_backend=backend)
        assert_alone_parity(cfg, packed, reqs, sched,
                            kernel_backend=backend)


# -- token accounting --------------------------------------------------------

def test_scheduler_token_accounting(dense):
    """Every request's budget is served, the first token of each from its
    prefill and the rest from decode steps; occupancy and the latency
    percentiles are well formed."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=5,
                         prompt_lens=(4, 8), budgets=(2, 8))
    sched = serve_scheduled(cfg, params, reqs, slots=2)
    assert sched.useful_tokens == sum(r.max_new_tokens for r in reqs)
    assert sched.decode_tokens == sched.useful_tokens - len(reqs)
    assert 0 < sched.occupancy <= 1
    assert sched.latency_steps["p50"] <= sched.latency_steps["p99"]


# -- collect_logits memory regression ----------------------------------------

def test_collect_logits_bounded_device_memory(dense):
    """collect_logits=True used to retain EVERY step's full (slots, vocab)
    logits on device until the run ended — device memory grew linearly with
    run length.  Pin the fix: while the loop runs, the number of live
    vocab-column device arrays stays flat instead of tracking step count."""
    cfg, m, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=6,
                         prompt_lens=(4, 8), budgets=(6, 10))
    comp = compile_sched_steps(cfg, max_seq=20)

    def live_vocab_arrays():
        return sum(1 for a in jax.live_arrays()
                   if a.ndim == 2 and a.shape[-1] == cfg.vocab_size)

    counts = []
    orig_decode = comp.decode

    def counting_decode(*args, **kw):
        out = orig_decode(*args, **kw)
        counts.append(live_vocab_arrays())
        return out

    spied = dataclasses.replace(comp, decode=counting_decode)
    sched = serve_scheduled(cfg, params, reqs, slots=2, max_seq=20,
                            compiled=spied, collect_logits=True)
    assert sched.steps >= 6                      # a real multi-step run
    assert len(counts) == sched.steps
    # flat, not linear: the leak made this grow by ~1 per step
    assert max(counts) - min(counts) <= 2, counts
    # and the logits still arrive, host-side, one row per generated token
    for q in reqs:
        lg = sched.requests[q.rid]["logits"]
        assert isinstance(lg, np.ndarray)
        assert lg.shape == (q.max_new_tokens, cfg.vocab_size)


def test_collect_logits_matches_alone_serving(dense):
    """The incrementally-fetched logits are the same ones the plain loop
    returns serving the request alone (active rows only, in order)."""
    cfg, m, params = dense
    rng = np.random.default_rng(8)
    req = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, (6,))
                  .astype(np.int32), max_new_tokens=4)
    sched = serve_scheduled(cfg, params, [req], slots=2, max_seq=16,
                            collect_logits=True)
    _, alone = plain_serve(cfg, params, req.prompt[None], gen=4,
                           max_seq=16, logits=True)
    np.testing.assert_allclose(sched.requests[0]["logits"], alone[0],
                               rtol=1e-5, atol=1e-5)


# -- compile-once decode with the decode-shaped kernels ----------------------

def test_decode_compiles_once_with_pallas_kernels(dense):
    """The slot-aware pallas decode path (GEMV dispatch + decode attention
    with the occupancy vector traced) must keep the one-executable
    contract across admissions/completions, on packed weights."""
    from repro.configs.base import QuantConfig
    from repro.core import pack_model, quantize_model
    from repro.data.pipeline import DataConfig, calibration_batches
    cfg, m, params = dense
    qcfg = QuantConfig(bits=4, group_size=32)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=10, global_batch=2,
                    seed=0)
    calib = [{"tokens": jnp.asarray(b["tokens"][:, :-1])}
             for b in calibration_batches(dc, 1, 2)]
    pq, qmeta, _ = quantize_model(cfg, params, calib, qcfg, method="none",
                                  init="rtn")
    packed = pack_model(cfg, pq, qmeta, qcfg)
    reqs = make_workload(cfg.vocab_size, n_requests=4, seed=7,
                         prompt_lens=(4, 8), budgets=(1, 5), mean_gap=2.0)
    comp = compile_sched_steps(cfg, max_seq=14, kernel_backend="pallas")
    sched = serve_scheduled(cfg, packed, reqs, slots=2, max_seq=14,
                            compiled=comp)
    assert sched.steps > 0
    assert comp.decode._cache_size() == 1


# -- host spans in the profiler's trace --------------------------------------

def _traced_spans(log_dir, run):
    """Run ``run()`` under the profiler; return its result and the serve
    loop's host spans as (name, start_ns, end_ns, stats), in start order."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    return out, sorted(spans, key=lambda x: x[1])


def _inside(spans, name, outer):
    return [x for x in spans
            if x[0] == name and outer[1] <= x[1] and x[2] <= outer[2]]


@pytest.mark.parametrize("store", ["dense", "paged"])
def test_serve_spans_mark_each_phase(dense, tmp_path, store):
    """Whole-prefill admissions: one ``serve.admit`` per request holding
    exactly one prefill, first-token sync and install; one ``serve.decode``
    step span per decode step, numbered in order; one drain and one fetch;
    and the tokens are those of the same run without the profiler."""
    cfg, _, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=5, seed=11,
                         prompt_lens=(4, 8), budgets=(1, 6), mean_gap=1.0)
    kw = dict(slots=2, max_seq=16, store=store, page_size=4)
    plain = serve_scheduled(cfg, params, reqs, **kw)
    res, spans = _traced_spans(
        tmp_path, lambda: serve_scheduled(cfg, params, reqs, **kw))
    for q in reqs:
        np.testing.assert_array_equal(res.requests[q.rid]["tokens"],
                                      plain.requests[q.rid]["tokens"])

    admits = [x for x in spans if x[0] == S.SPAN_ADMIT]
    assert sorted(x[3]["rid"] for x in admits) == sorted(q.rid for q in reqs)
    plens = {q.rid: len(q.prompt) for q in reqs}
    for a in admits:
        assert 0 <= a[3]["slot"] < kw["slots"]
        assert a[3]["plen"] == plens[a[3]["rid"]]
        for child in (S.SPAN_PREFILL, S.SPAN_FIRST_TOKEN, S.SPAN_INSTALL):
            assert len(_inside(spans, child, a)) == 1, (child, a)
    assert sum(x[0] == S.SPAN_PREFILL for x in spans) == len(reqs)
    assert not any(x[0] == S.SPAN_CHUNK for x in spans)

    decodes = [x for x in spans if x[0] == S.SPAN_DECODE]
    assert [x[3]["step_num"] for x in decodes] == list(range(res.steps))
    assert all(1 <= x[3]["live"] <= kw["slots"] for x in decodes)
    assert sum(x[3]["live"] for x in decodes) == res.decode_tokens
    # no admission or decode span overlaps another
    loop = sorted(admits + decodes, key=lambda x: x[1])
    assert all(a[2] <= b[1] for a, b in zip(loop, loop[1:]))

    drain = [x for x in spans if x[0] == S.SPAN_DRAIN]
    fetch = [x for x in spans if x[0] == S.SPAN_FETCH]
    assert len(drain) == len(fetch) == 1
    assert fetch[0][3]["steps"] == res.steps
    assert decodes[-1][2] <= drain[0][1] <= drain[0][2] <= fetch[0][1]


@pytest.mark.parametrize("store", ["dense", "paged"])
def test_serve_spans_one_per_prefill_chunk(dense, tmp_path, store):
    """Chunked prefill: one ``serve.chunk`` per chunk, carrying the
    request, its slot and the chunk's token range; no whole-prefill
    admission spans."""
    cfg, _, params = dense
    reqs = make_workload(cfg.vocab_size, n_requests=3, seed=12,
                         prompt_lens=(5, 11), budgets=(2, 4))
    chunk = 4
    kw = dict(slots=2, max_seq=16, store=store, page_size=4,
              prefill_chunk=chunk)
    plain = serve_scheduled(cfg, params, reqs, **kw)
    res, spans = _traced_spans(
        tmp_path, lambda: serve_scheduled(cfg, params, reqs, **kw))
    for q in reqs:
        np.testing.assert_array_equal(res.requests[q.rid]["tokens"],
                                      plain.requests[q.rid]["tokens"])
    chunks = [x[3] for x in spans if x[0] == S.SPAN_CHUNK]
    want = [(q.rid, lo, min(lo + chunk, len(q.prompt)))
            for q in sorted(reqs, key=lambda r: (r.arrival, r.rid))
            for lo in range(0, len(q.prompt), chunk)]
    assert [(c["rid"], c["start"], c["end"]) for c in chunks] == want
    assert all(0 <= c["slot"] < kw["slots"] for c in chunks)
    assert not any(x[0] == S.SPAN_ADMIT for x in spans)
    assert sum(x[0] == S.SPAN_DECODE for x in spans) == res.steps
