"""Slot-based continuous-batching request scheduler (the real serve loop).

``launch/serve.py``'s old loop decoded every request in lock-step for a fixed
``gen``: no completion, no admission, every heterogeneous batch paid for its
longest member.  This module is the scheduler that docstring promised:

  * a FIFO **request queue** with per-request arrival times (decode-step
    units, from a seeded plan — see :func:`make_workload`);
  * a fixed number of **slots**, each owning one lane of the batched cache
    (the per-family cache layout — which leaves are per-token, where the
    slot axis sits — is DECLARED by ``Model.cache_spec``, a
    ``models.common.CacheSpec``; ``write_slot`` moves a prefilled request's
    state into its slot under the dense store, the paged install step
    scatters it into pool pages under the paged store);
  * **ragged lengths**: each request prefills at its true prompt length
    (batch-of-1, one jit specialization per distinct length) and decodes
    until its own token budget, not the batch max;
  * **completion masking**: a finished slot's token, write cursor and KV
    state are frozen on device (``launch.steps.make_sched_steps``) and its
    logits are never recorded again;
  * **admission mid-decode**: a freed slot is handed the next queued request
    without stopping the other slots;
  * a **compile-once decode step**: fixed slot count, occupancy as a traced
    bool vector — the jit cache stays at one entry across every occupancy
    change (pinned by ``tests/test_scheduler.py``).

The decode loop is sync-free: completions are token-budget driven (host-known
at admission), so the only host round-trips are one per admission (the first
generated token) and one final sync.  Per-step token device arrays are
fetched after the loop ends.  ``collect_logits=True`` fetches each step's
logits to host eagerly instead — retaining every step's full (slots, vocab)
logits on device grows HBM linearly with run length — so logit-collecting
runs sync per step and are NOT timing-comparable (parity and debug callers
don't time themselves anyway).

Per-request outputs are bit-identical to serving the same request alone
through ``serve_requests`` at the same cache width: active rows see exactly
the arguments the plain loop passes, and every op in the decode path is
batch-row independent.  (Exception: MoE capacity dispatch couples rows by
construction — tokens compete for per-expert capacity slots — so MoE gets
determinism, not alone-parity.)

``store="paged"`` swaps the dense per-slot lanes for a vLLM-style paged KV
cache (``models.common.PagedCacheStore``): token leaves live in a fixed
pool of ``page_size``-token pages, admission allocates a lifetime's worth
of pages (waiting in queue instead of failing when the pool is tight), and
the page table reaches the decode step as a device array.  Because the
gathered virtual cache spans the FULL logical width and junk beyond
``kv_len`` is masked to exactly -1e30 in dense and paged alike, paged
per-request outputs stay BIT-identical to the dense store's.
``prefill_chunk > 0`` additionally splits chunkable families' prompts into
chunks interleaved one-per-iteration with decode (store-agnostic — chunk
steps run at full cache width, so dense and paged chunked prefill remain
bit-identical at the same chunk schedule), and ``share_prefix=True`` lets
paged chunked admission reuse full prompt-prefix pages copy-on-write.

``serve_scheduled`` marks the phases of its loop with ``jax.profiler``
annotations (the ``SPAN_*`` names below).  Outside a profiler trace they
cost on the order of a microsecond of host time each; inside one they land
in the same trace file as the device's operations, on the same clock, so
an idle gap of the chip can be put down to the phase of the loop the host
was in.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.mesh import validate_single_pod
from repro.launch.steps import (cache_donate_argnums, make_paged_install_step,
                                make_sched_steps)
from repro.models.common import (DenseCacheStore, PagedCacheStore, write_slot)


# Host spans of ``serve_scheduled`` (event name, stats):
SPAN_ADMIT = "serve.admit"              # one whole-prefill admission: rid, slot, plen
SPAN_PREFILL = "serve.prefill"          # ├ B=1 cache init + prefill dispatch
SPAN_FIRST_TOKEN = "serve.first_token"  # ├ the first token's device->host sync
SPAN_INSTALL = "serve.install"          # └ slot install + its block_until_ready
SPAN_CHUNK = "serve.chunk"              # one chunked-prefill iteration: rid, slot, start, end
SPAN_DECODE = "serve.decode"            # one decode step's dispatch + bookkeeping: step_num, live
SPAN_DRAIN = "serve.drain"              # the final wait for the last decode step
SPAN_FETCH = "serve.fetch"              # after the loop, the per-step tokens to host: steps


@dataclasses.dataclass(frozen=True)
class Request:
    """One queued generation request.

    ``arrival`` is in scheduler-clock units (decode steps): the request is
    admissible once the scheduler has dispatched that many decode steps.
    ``extras`` carries additional per-request prefill inputs for multimodal
    families (``frames`` for encdec, ``patches`` for vlm), unbatched.
    """
    rid: int
    prompt: np.ndarray                  # (plen,) int32
    max_new_tokens: int
    arrival: int = 0
    extras: Optional[Dict[str, np.ndarray]] = None


def _push(host_arr: np.ndarray):
    """Host->device transfer of a buffer the scheduler will keep MUTATING.

    jax's CPU client zero-copies 64-byte-aligned numpy buffers into device
    arrays (alignment is allocator luck for small arrays), so handing it
    ``active_h`` directly would let later in-place mutations retroactively
    corrupt the mask a dispatched step still references — a sporadic,
    alignment-dependent heisenbug.  Always transfer a private copy that
    nothing ever writes again — via ``device_put``, the explicit-transfer
    form the sanitizer's ``transfer_guard("disallow")`` permits."""
    return jax.device_put(host_arr.copy())


def _i32(v) -> jax.Array:
    """Explicitly placed int32 scalar: python ints handed to a jitted step
    as traced args are device_put implicitly per call, which the sanitizer's
    transfer_guard rejects; this is the explicit-transfer spelling."""
    return jax.device_put(np.int32(v))


# jitted single-slot scatter for the admission bookkeeping: eager
# ``a.at[s].set(v)`` device_puts its scalar index/value per call, which the
# sanitizer's transfer_guard rejects; the operands enter via explicit
# device_put instead
_set_slot_jit = jax.jit(lambda a, s, v: a.at[s].set(v))


def _set_slot(a, s: int, v: int):
    return _set_slot_jit(a, _i32(s), _i32(v))


@dataclasses.dataclass(frozen=True)
class SchedSteps:
    """Jitted step set for one (arch, max_seq, backend, act_bits, store)
    config."""
    model: Any
    prefill: Any              # (params, batch, cache[, start_pos, ptab])
    decode: Any               # (params, cache, tok, pos, active[, ptab])
    write_slot: Any
    install: Any = None       # paged admission (cache, c1, slot, ptab_row)
    page_size: int = 0


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """The one result surface every serve entry point returns
    (``serve_requests``, ``serve_scheduled``, ``serve_lockstep``).

    ``requests`` maps rid -> per-request record (``tokens`` (gen,) int32,
    ``logits`` (gen, V) or None, admission/finish bookkeeping where the
    mode tracks it).  ``latency_steps`` holds mean/p50/p90/p99 percentiles
    in decode-step units.  ``cache_stats`` is the cache store's accounting
    (``CacheStore.stats()``: bytes always; page-pool counters when paged).
    Mode-specific extras (e.g. lock-step's wasted-token accounting) ride in
    ``extra``.  Mapping-style ``result["key"]`` access resolves attributes
    (falling back to ``extra``) so result handling can migrate gradually.

    The timings are host seconds, not a split of the device's time.  For
    ``serve_scheduled``, ``prefill_secs`` is the host time spent inside
    admissions, from the prefill's dispatch through the first token's sync
    and the slot install; that sync also waits for every decode step
    dispatched ahead of it, so it counts decode work too.  ``decode_secs``
    is the rest of the loop, ``prefill_tok_s`` and ``decode_tok_s`` are
    prompt and decode tokens over those seconds.  The device's own split is
    in a profiler trace: the ``SPAN_*`` host spans beside the prefill and
    decode programs' executions.  ``serve_requests`` blocks on its prefill
    before decoding, so its two timings do split the work (``serve_lockstep``
    sums them over its batches).
    """
    mode: str                               # "uniform"|"scheduled"|"lockstep"
    store: str                              # "dense" | "paged"
    requests: Dict[int, Dict[str, Any]]
    slots: int
    max_seq: int
    steps: int
    useful_tokens: int
    decode_tokens: int
    prefill_secs: float
    decode_secs: float
    prefill_tok_s: float
    decode_tok_s: float
    occupancy: float
    latency_steps: Dict[str, float]
    cache_stats: Dict[str, Any]
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __getitem__(self, key: str):
        if key in self.extra:
            return self.extra[key]
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    # reprolint: ok[host-sync] — cold accessor over already-fetched host arrays; runs after the timed loop
    def token_matrix(self) -> np.ndarray:
        """(B, gen) token ids, rids in sorted order — uniform-budget runs
        only (ragged budgets cannot stack; use ``requests`` directly)."""
        rids = sorted(self.requests)
        return np.stack([np.asarray(self.requests[r]["tokens"], np.int32)
                         for r in rids], 0)

    # reprolint: ok[host-sync] — cold accessor over already-fetched host arrays; runs after the timed loop
    def logits_matrix(self) -> Optional[np.ndarray]:
        """(B, gen, V) float32 logits, or None when not collected."""
        rids = sorted(self.requests)
        if not rids or self.requests[rids[0]].get("logits") is None:
            return None
        return np.stack([np.asarray(self.requests[r]["logits"], np.float32)
                         for r in rids], 0)

    @property
    def tokens(self) -> np.ndarray:
        return self.token_matrix()

    @property
    def logits(self) -> Optional[np.ndarray]:
        return self.logits_matrix()


# reprolint: ok[host-sync] — pure host statistics over python floats; no device values involved
def _latency_stats(latencies) -> Dict[str, float]:
    lat = np.asarray(latencies, np.float64)
    return {"mean": float(lat.mean()), "p50": float(np.percentile(lat, 50)),
            "p90": float(np.percentile(lat, 90)),
            "p99": float(np.percentile(lat, 99))}


def make_workload(vocab_size: int, *, n_requests: int, seed: int,
                  prompt_lens=(8, 32), budgets=(2, 24),
                  mean_gap: float = 1.0, long_frac: float = 0.0,
                  long_prompt_lens=None, long_budgets=None) -> List[Request]:
    """Seeded heterogeneous request plan: mixed prompt lengths, mixed token
    budgets, Poisson inter-arrival gaps in decode-step units.  A pure
    function of its arguments, so the same seed yields the same plan on
    every run — the admission-determinism tests and the bench gate both
    lean on that.

    ``long_frac > 0`` makes the plan LONG-TAILED: that fraction of requests
    draws from ``long_prompt_lens``/``long_budgets`` instead — the
    heterogeneous-length regime where dense per-slot lanes waste the most
    memory and the paged store's sizing advantage shows up."""
    rng = np.random.default_rng(seed)
    t = 0
    reqs = []
    for rid in range(n_requests):
        is_long = long_frac > 0 and rng.random() < long_frac
        pl = long_prompt_lens if is_long else prompt_lens
        bu = long_budgets if is_long else budgets
        plen = int(rng.integers(pl[0], pl[1] + 1))
        budget = int(rng.integers(bu[0], bu[1] + 1))
        prompt = rng.integers(0, vocab_size, (plen,)).astype(np.int32)
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=budget,
                            arrival=t))
        t += int(rng.poisson(mean_gap))
    return reqs


def _prefill_len(cfg: ModelConfig, req: Request) -> int:
    """Cache positions a request's prefill consumes: its prompt, plus the
    image-patch prefix for VLMs (patches share the decoder cache)."""
    extra = cfg.num_patches if cfg.family == "vlm" else 0
    return len(req.prompt) + extra


# per-configuration jitted step sets: every run/repeat over the same
# (cfg, width, backend, store, mesh) must reuse ONE SchedSteps — fresh
# jit wrappers defeat the tracing cache (the PR 4 recompile class), and
# the memoized serve_mesh keeps mesh identity stable for the key.
_SCHED_STEP_CACHE: dict = {}


def compile_sched_steps(cfg: ModelConfig, *, max_seq: int,
                        kernel_backend=None, act_bits=None,
                        page_size: int = 0,
                        decode_attn_chunk: int = 1 << 30,
                        mesh=None, tp_shard: bool = False) -> SchedSteps:
    """Jit-wrap the scheduler's step set ONCE per serving configuration —
    memoized per (cfg, width, backend, act_bits, store, mesh, tp_shard),
    so repeated calls hand back the SAME jitted steps instead of retracing.
    ``page_size > 0`` builds the paged-store step set (page-table-aware
    decode plus the paged admission install step).

    ``mesh`` must be single-pod: the scheduler has no cross-pod path (the
    pipelined quantization walk is the only multi-pod consumer) — give
    each pod its own submesh via ``launch.mesh.pod_submeshes`` instead.
    ``tp_shard=True`` routes prefill/decode through the tensor-parallel
    ServeSpec contract (shard_map over the mesh's ``model`` axis); the
    admission steps (``write_slot``, paged install) stay plain jit —
    GSPMD reshards their outputs to the decode step's specs."""
    validate_single_pod(mesh, "compile_sched_steps")
    key = (cfg, max_seq, kernel_backend, act_bits, page_size,
           decode_attn_chunk, mesh, tp_shard)
    if key not in _SCHED_STEP_CACHE:
        model, pstep, dstep = make_sched_steps(
            cfg, mesh, max_seq=max_seq, act_bits=act_bits,
            kernel_backend=kernel_backend, page_size=page_size,
            decode_attn_chunk=decode_attn_chunk, tp_shard=tp_shard)
        install = None
        if page_size:
            install = jax.jit(
                make_paged_install_step(model, page_size=page_size),
                static_argnames=("plen",),
                donate_argnums=cache_donate_argnums(0))
        _SCHED_STEP_CACHE[key] = SchedSteps(
            model=model,
            prefill=jax.jit(pstep),
            decode=jax.jit(dstep, donate_argnums=cache_donate_argnums(1)),
            write_slot=jax.jit(write_slot,
                               donate_argnums=cache_donate_argnums(0)),
            install=install, page_size=page_size)
    return _SCHED_STEP_CACHE[key]


def serve_scheduled(cfg: ModelConfig, params, requests: List[Request], *,
                    slots: int, max_seq: Optional[int] = None,
                    kernel_backend=None, act_bits=None,
                    collect_logits: bool = False,
                    compiled: Optional[SchedSteps] = None,
                    store: str = "dense", page_size: int = 16,
                    num_pages: Optional[int] = None,
                    prefill_chunk: int = 0,
                    share_prefix: bool = False, mesh=None,
                    tp_shard: bool = False) -> ServeResult:
    """Serve ``requests`` through the slot scheduler.

    Returns a :class:`ServeResult`; per-request records are keyed by rid
    (``tokens`` is exactly ``max_new_tokens`` long: the prefill token plus
    its decode steps).  ``decode_tok_s`` counts USEFUL tokens only — every
    request's own budget, which is also the number actually generated; the
    lock-step baseline reports the same numerator so the two compose into
    an apples-to-apples goodput gate.

    ``store="paged"``: token-leaf KV lives in a pool of ``num_pages``
    pages of ``page_size`` tokens (default pool: capacity parity with the
    dense store); admission waits in queue when the pool is tight instead
    of failing.  ``prefill_chunk > 0``: chunkable families' prompts prefill
    in chunks of that many tokens, one chunk interleaved per decode
    iteration (non-chunkable families fall back to whole prefill at
    admission).  ``share_prefix=True`` (paged + chunked only): full
    prompt-prefix pages are shared copy-on-write across requests."""
    if slots < 1:
        raise ValueError(f"need at least one slot, got {slots}")
    if store not in ("dense", "paged"):
        raise ValueError(f"unknown store {store!r} (dense|paged)")
    paged = store == "paged"
    order = sorted(requests, key=lambda r: (r.arrival, r.rid))
    if max_seq is None:
        max_seq = max(_prefill_len(cfg, r) + r.max_new_tokens
                      for r in order)
        if paged:                       # page-align the derived width
            max_seq += (-max_seq) % page_size
    for r in order:
        if r.max_new_tokens < 1:
            raise ValueError(f"request {r.rid}: max_new_tokens must be >= 1")
        if _prefill_len(cfg, r) + r.max_new_tokens > max_seq:
            raise ValueError(
                f"request {r.rid}: prefill length ({_prefill_len(cfg, r)}) "
                f"+ budget ({r.max_new_tokens}) exceeds max_seq ({max_seq})")
    steps_ = compiled if compiled is not None else compile_sched_steps(
        cfg, max_seq=max_seq, kernel_backend=kernel_backend,
        act_bits=act_bits, page_size=page_size if paged else 0,
        mesh=mesh, tp_shard=tp_shard)
    if steps_.page_size != (page_size if paged else 0):
        raise ValueError(
            f"compiled step set was built for page_size={steps_.page_size}, "
            f"run wants {'page_size=%d' % page_size if paged else 'dense'}")
    model = steps_.model
    spec = model.cache_spec

    # TP serving: commit params/cache — and every host push below — to the
    # ServeSpec placement ONCE.  Anything left committed to device 0 would
    # be resharded onto the mesh at every jitted step dispatch: an implicit
    # device-to-device transfer per step, slow and rejected by the serving
    # sanitizer's transfer_guard.
    tp_rep = None
    if tp_shard and mesh is not None:
        from repro.launch.sharding import ServeSpec
        tp_spec = ServeSpec.for_mesh(mesh, cfg)
        if tp_spec.active:
            tp_plan = tp_spec.plan(params)
            params = tp_spec.place_params(params, tp_plan)
            tp_rep = tp_spec.replicated()

    def push(a):
        return (jax.device_put(a.copy(), tp_rep) if tp_rep is not None
                else _push(a))

    def put(a):
        return (jax.device_put(a, tp_rep) if tp_rep is not None
                else jax.device_put(a))

    def i32(v):
        return (jax.device_put(np.int32(v), tp_rep) if tp_rep is not None
                else _i32(v))

    def set_slot(a, s, v):
        return _set_slot_jit(a, i32(s), i32(v))

    def place_cache(c):
        return (tp_spec.place_cache(spec, c, tp_plan)
                if tp_rep is not None else c)

    if paged:
        if num_pages is None:
            num_pages = slots * (max_seq // page_size)   # dense capacity
        cstore = PagedCacheStore(model, slots=slots, max_seq=max_seq,
                                 page_size=page_size, num_pages=num_pages)
        for r in order:     # requests the pool can NEVER hold fail fast
            need = cstore.pages_needed(_prefill_len(cfg, r)
                                       + r.max_new_tokens)
            if need > num_pages:
                raise ValueError(
                    f"request {r.rid} needs {need} pages but the pool only "
                    f"has {num_pages} — it can never be admitted; raise "
                    f"num_pages or lower the request's length")
    else:
        cstore = DenseCacheStore(model, slots=slots, max_seq=max_seq)
    cache = place_cache(cstore.cache)
    ptab_d = push(cstore.ptab_h) if paged else None
    # chunked prefill applies to chunkable families only; prefix sharing
    # additionally needs the paged store (pages are the sharing unit)
    chunk_ok = prefill_chunk > 0 and spec.chunkable
    share_ok = share_prefix and paged and chunk_ok and spec.shareable

    tok = push(np.zeros((slots,), np.int32))
    pos = push(np.zeros((slots,), np.int32))
    active_h = np.zeros((slots,), bool)        # host mirror of occupancy
    active_d = push(active_h)
    slot_rid = np.full((slots,), -1, np.int64)
    remaining = np.zeros((slots,), np.int64)   # decode steps left per slot
    res = {r.rid: {"arrival": r.arrival, "admit_step": None,
                   "finish_step": None, "tokens": [], "logits": []}
           for r in order}
    pending = deque(order)
    inflight = None       # at most one chunked prefill in flight
    trace = []            # (active snapshot, slot->rid snapshot, tok)
    t = 0                 # scheduler clock, in decode steps dispatched
    steps = 0
    occupancy_acc = 0
    prefill_secs = 0.0
    prompt_tokens = sum(_prefill_len(cfg, r) for r in order)
    t_start = time.time()

    def finish_prefill(s, req, tok0, lg1):
        """Common post-prefill bookkeeping (whole or final chunk)."""
        nonlocal tok, pos
        tok = set_slot(tok, s, tok0)
        pos = set_slot(pos, s, _prefill_len(cfg, req))
        r = res[req.rid]
        r["admit_step"] = t
        r["tokens"].append(tok0)
        if collect_logits:
            # reprolint: ok[host-sync] — admission-time logits fetch; rides the per-admission sync below
            r["logits"].append(np.asarray(jax.device_get(lg1[0]),
                                          np.float32))
        if share_ok:
            cstore.register_prefix(s, req.prompt)
        if req.max_new_tokens == 1:
            r["finish_step"] = t                 # done at prefill
            cstore.release(s)
            return False
        slot_rid[s] = req.rid
        remaining[s] = req.max_new_tokens - 1
        active_h[s] = True
        return True

    while pending or active_h.any() or inflight is not None:
        # ---- admission: queued requests into free slots -------------------
        dirty = ptab_dirty = False
        while pending and pending[0].arrival <= t:
            busy = active_h.copy()
            if inflight is not None:
                if chunk_ok:
                    break            # one in-flight chunked prefill at a time
                busy[inflight["slot"]] = True
            free = np.flatnonzero(~busy)
            if len(free) == 0:
                break
            req = pending[0]
            s = int(free[0])
            total = _prefill_len(cfg, req) + req.max_new_tokens
            plan = cstore.try_admit(s, total, prompt=req.prompt,
                                    share=share_ok)
            if plan is None:
                break                # pool exhausted: FCFS head waits
            pending.popleft()
            ptab_dirty |= paged
            if chunk_ok:
                # slot + pages reserved; the prompt prefills one chunk per
                # loop iteration, interleaved with decode below
                inflight = {"req": req, "slot": s,
                            "cursor": plan.shared_tokens,
                            "c1": (None if paged
                                   else place_cache(model.init_cache(1, max_seq)))}
                continue
            # ---- whole prefill at full cache width ------------------------
            tp0 = time.time()
            with jax.profiler.TraceAnnotation(
                    SPAN_ADMIT, rid=req.rid, slot=s,
                    plen=_prefill_len(cfg, req)):
                with jax.profiler.TraceAnnotation(SPAN_PREFILL):
                    batch = {"tokens": put(req.prompt[None])}
                    for k, v in (req.extras or {}).items():
                        batch[k] = put(v[None])
                    c1 = place_cache(model.init_cache(1, max_seq))
                    lg1, c1 = steps_.prefill(params, batch, c1)
                with jax.profiler.TraceAnnotation(SPAN_FIRST_TOKEN):
                    # reprolint: ok[host-sync] — the only per-admission sync (counted); explicit device_get so transfer_guard allows it
                    tok0 = int(np.asarray(jax.device_get(jnp.argmax(lg1, -1)))[0])
                with jax.profiler.TraceAnnotation(SPAN_INSTALL):
                    if paged:
                        cache = steps_.install(cache, c1, i32(s),
                                               push(cstore.ptab_h[s]),
                                               plen=_prefill_len(cfg, req))
                    else:
                        cache = steps_.write_slot(cache, c1, i32(s))
                    # the argmax sync above already drained the dispatch
                    # queue, so blocking here charges ONLY the slot install
                    # to the admission window instead of letting it leak
                    # into decode_secs
                    jax.block_until_ready(cache)   # reprolint: ok[host-sync] — admission-window timing boundary
                dirty |= finish_prefill(s, req, tok0, lg1)
                ptab_dirty |= paged  # budget-1 admissions release pages
            prefill_secs += time.time() - tp0
        # ---- one prefill chunk for the in-flight request ------------------
        if inflight is not None:
            tp0 = time.time()
            req, s = inflight["req"], inflight["slot"]
            cur = inflight["cursor"]
            plen = len(req.prompt)   # chunkable families are text-only
            end = min(cur + prefill_chunk, plen)
            with jax.profiler.TraceAnnotation(SPAN_CHUNK, rid=req.rid, slot=s,
                                              start=cur, end=end):
                chunk = {"tokens": put(req.prompt[None, cur:end])}
                if paged:
                    lg1, cache = steps_.prefill(params, chunk, cache,
                                                i32(cur),
                                                push(cstore.ptab_h[s:s + 1]))
                else:
                    lg1, inflight["c1"] = steps_.prefill(params, chunk,
                                                         inflight["c1"],
                                                         i32(cur))
                inflight["cursor"] = end
                if end == plen:
                    # reprolint: ok[host-sync] — per-admission sync, chunked path (same contract as above)
                    tok0 = int(np.asarray(jax.device_get(jnp.argmax(lg1, -1)))[0])
                    if not paged:
                        cache = steps_.write_slot(cache, inflight["c1"],
                                                  i32(s))
                    jax.block_until_ready(cache)   # reprolint: ok[host-sync] — admission-window timing boundary
                    dirty |= finish_prefill(s, req, tok0, lg1)
                    ptab_dirty |= paged
                    inflight = None
                else:
                    jax.block_until_ready(lg1)   # reprolint: ok[host-sync] — honest prefill attribution
            prefill_secs += time.time() - tp0
        if not active_h.any():
            if not pending and inflight is None:
                break
            if inflight is None:
                if pending[0].arrival <= t:
                    # nothing active or in flight -> every page is free, and
                    # per-request pool fit was pre-validated; an admission
                    # failure here is an allocator invariant break
                    raise RuntimeError(
                        f"scheduler stalled: request {pending[0].rid} not "
                        f"admissible with an idle pool "
                        f"(stats: {cstore.stats()})")
                t = pending[0].arrival           # idle: jump to next arrival
            else:
                t += 1                           # chunk-only iteration
            continue
        live = int(active_h.sum())
        with jax.profiler.StepTraceAnnotation(SPAN_DECODE, step_num=steps,
                                              live=live):
            if dirty:
                active_d = push(active_h)
            if ptab_dirty:
                ptab_d = push(cstore.ptab_h)
            # ---- one masked decode step over every slot -------------------
            if paged:
                logits, tok, pos, cache = steps_.decode(
                    params, cache, tok, pos, active_d, ptab_d)
            else:
                logits, tok, pos, cache = steps_.decode(
                    params, cache, tok, pos, active_d)
            if collect_logits:
                # eager per-step fetch of ACTIVE rows only: bounded device
                # memory (regression-tested in tests/test_scheduler.py)
                # reprolint: ok[host-sync] — eager fetch only when collect_logits=True; opt-in debugging path
                lg_np = np.asarray(jax.device_get(logits), np.float32)
                for s in np.flatnonzero(active_h):
                    res[slot_rid[s]]["logits"].append(lg_np[s])
            del logits
            trace.append((active_h.copy(), slot_rid.copy(), tok))
            steps += 1
            occupancy_acc += live
            t += 1
            # ---- budget completions (host-known, zero sync) ---------------
            done = active_h & (remaining == 1)
            remaining[active_h] -= 1
            if done.any():
                for s in np.flatnonzero(done):
                    res[slot_rid[s]]["finish_step"] = t
                    slot_rid[s] = -1
                    cstore.release(int(s))
                active_h[done] = False
                active_d = push(active_h)
                if paged:
                    ptab_d = push(cstore.ptab_h)

    with jax.profiler.TraceAnnotation(SPAN_DRAIN):
        tok.block_until_ready()                  # reprolint: ok[host-sync] — closes the timed region
    total_secs = time.time() - t_start
    decode_secs = max(total_secs - prefill_secs, 1e-9)

    # ---- reconstruct per-request streams (host transfers OFF the clock) ---
    with jax.profiler.TraceAnnotation(SPAN_FETCH, steps=len(trace)):
        for mask, rids, tok_d in trace:
            # reprolint: ok[host-sync] — off-clock stream reconstruction; timed region already closed
            tok_np = np.asarray(jax.device_get(tok_d))
            for s in np.flatnonzero(mask):
                res[rids[s]]["tokens"].append(int(tok_np[s]))

    useful = 0
    latencies = []
    for r in order:
        rr = res[r.rid]
        # reprolint: ok[host-sync] — host python list → array; no device values involved
        rr["tokens"] = np.asarray(rr["tokens"], np.int32)
        assert rr["tokens"].shape == (r.max_new_tokens,)
        rr["logits"] = (np.stack(rr["logits"], 0)
                        if rr["logits"] else None)
        rr["latency_steps"] = rr["finish_step"] - rr["arrival"]
        latencies.append(rr["latency_steps"])
        useful += r.max_new_tokens
    decode_tokens = useful - len(order)          # first tokens come from prefill
    return ServeResult(
        mode="scheduled", store=cstore.kind, requests=res,
        slots=slots, max_seq=max_seq, steps=steps,
        useful_tokens=useful, decode_tokens=decode_tokens,
        prefill_secs=prefill_secs, decode_secs=decode_secs,
        prefill_tok_s=prompt_tokens / max(prefill_secs, 1e-9),
        decode_tok_s=decode_tokens / decode_secs,
        occupancy=(occupancy_acc / (steps * slots)) if steps else 0.0,
        latency_steps=_latency_stats(latencies),
        cache_stats=cstore.stats(),
        extra={"prefill_chunk": prefill_chunk if chunk_ok else 0,
               "share_prefix": share_ok},
    )


def serve_lockstep(cfg: ModelConfig, model, params, requests: List[Request],
                   *, slots: int, kernel_backend=None, act_bits=None,
                   compiled=None, pad_id: int = 0) -> ServeResult:
    """The pre-scheduler serve loop as a baseline, at the SAME cache width.

    FCFS static batching: requests are grouped ``slots`` at a time in
    arrival order; each batch pads every prompt to the batch max length and
    decodes in lock-step for the batch max budget — short requests pay for
    the batch's longest member, and padded rows decode garbage (exactly the
    deficiency the scheduler fixes; this baseline exists to be measured
    against, its outputs are not parity-gated).  Arrival gaps are ignored,
    which only flatters the baseline."""
    from repro.launch.serve import compile_serve_steps, serve_requests
    order = sorted(requests, key=lambda r: (r.arrival, r.rid))
    if compiled is None:
        compiled = compile_serve_steps(cfg, kernel_backend=kernel_backend,
                                       act_bits=act_bits)
    prefill_secs = decode_secs = 0.0
    raw_decode_tokens = 0
    prompt_tokens = 0
    max_width = 0
    steps = 0
    for i in range(0, len(order), slots):
        group = order[i:i + slots]
        plen = max(len(r.prompt) for r in group)
        gen = max(r.max_new_tokens for r in group)
        prompts = np.full((len(group), plen), pad_id, np.int32)
        for j, r in enumerate(group):
            prompts[j, :len(r.prompt)] = r.prompt
        st = serve_requests(cfg, model, params, prompts, gen=gen,
                            compiled=compiled, collect_logits=False)
        prefill_secs += st.prefill_secs
        decode_secs += st.decode_secs
        raw_decode_tokens += len(group) * (gen - 1)
        prompt_tokens += len(group) * plen
        max_width = max(max_width, plen + gen)
        steps += gen - 1
    useful = sum(r.max_new_tokens for r in order)
    decode_tokens = useful - len(order)
    decode_secs = max(decode_secs, 1e-9)
    # every request's latency is its group's padded span (batch max budget),
    # measured like the scheduler: decode steps from arrival-batch start
    lats = []
    for i in range(0, len(order), slots):
        group = order[i:i + slots]
        lats += [max(r.max_new_tokens for r in group)] * len(group)
    return ServeResult(
        mode="lockstep", store="dense", requests={},
        slots=slots, max_seq=max_width, steps=steps,
        useful_tokens=useful, decode_tokens=decode_tokens,
        prefill_secs=prefill_secs, decode_secs=decode_secs,
        prefill_tok_s=prompt_tokens / max(prefill_secs, 1e-9),
        # useful-token goodput: same numerator the scheduler reports
        decode_tok_s=decode_tokens / decode_secs,
        occupancy=(decode_tokens / raw_decode_tokens
                   if raw_decode_tokens else 0.0),
        latency_steps=_latency_stats(lats),
        cache_stats={"store": "dense"},
        extra={"raw_decode_tokens": raw_decode_tokens,
               "wasted_decode_tokens": raw_decode_tokens - decode_tokens},
    )
