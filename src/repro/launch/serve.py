"""Serving launcher: quantize (TesseraQ) then serve batched requests with
packed weights — the paper's deployment scenario (Table 8).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --quant W4A16g32 --requests 8 --prompt-len 32 --gen 16

``--method none`` skips quantization entirely and serves the plain FP
params (the fp16 baseline every Table 8 comparison is against);
``--backend pallas`` routes every QTensor matmul through the fused Pallas
dequant-matmul kernel instead of the XLA unpack path.

Two serve loops ship here:

* ``serve_requests`` — the UNIFORM lock-step loop: one batch, one shared
  prompt length, a fixed ``gen`` for every row, no completion or admission.
  It is the right tool for homogeneous benches (and is the bit-identical
  parity anchor the serving benchmarks pin), and the wrong tool for
  heterogeneous traffic — every request pays for the batch's longest.
* ``--slots N`` routes serving through the slot-based continuous-batching
  scheduler (``repro.launch.scheduler``): per-request prompt lengths and
  token budgets, completion masking, admission of queued requests into
  freed slots mid-decode, one compile of the masked decode step.
"""
from __future__ import annotations

import argparse
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import pack_model, quantize_model, quantized_memory_report
from repro.core.qtensor import PACK_FACTOR
from repro.core.tesseraq import TesseraQConfig
from repro.data.pipeline import DataConfig, SyntheticCorpus, calibration_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import validate_single_pod
from repro.launch.steps import cache_donate_argnums, make_serve_steps
from repro.models import get_model

_QUANT_RE = re.compile(r"W(\d+)A(\d+)(?:g(\d+))?$")


def parse_quant(tag: str, kernel_backend: str = "xla") -> QuantConfig:
    """Parse a ``W<bits>A<act_bits>[g<group>]`` tag (e.g. ``W4A16g32``).

    Raises a descriptive ``ValueError`` on malformed tags instead of the
    bare ``AttributeError`` a failed regex match used to surface."""
    m = _QUANT_RE.match(tag)
    if m is None:
        raise ValueError(
            f"malformed quant tag {tag!r}: expected W<bits>A<act_bits>"
            f"[g<group>] with uppercase W/A, e.g. W4A16g32 or W2A16 "
            f"(per-channel)")
    bits, act, g = int(m.group(1)), int(m.group(2)), m.group(3)
    if bits not in PACK_FACTOR:
        raise ValueError(f"unsupported weight bits {bits} in {tag!r}: "
                         f"packing supports {sorted(PACK_FACTOR)}")
    if g is not None and int(g) <= 0:
        raise ValueError(f"group size must be a positive integer, got "
                         f"g{g} in {tag!r} (omit g for per-channel)")
    return QuantConfig(bits=bits, group_size=int(g) if g else None,
                       act_bits=None if act >= 16 else act,
                       kernel_backend=kernel_backend)


def build_params(cfg, params, qcfg: QuantConfig, data_cfg: DataConfig, *,
                 method: str, init: str, tcfg: TesseraQConfig,
                 calib_samples: int, verbose: bool = True):
    """Calibrate + pack, or pass FP params through for ``method="none"``.

    Returns (params_or_packed, memory_report_or_None)."""
    if method == "none":
        if verbose:
            print(f"[serve] serving FP {cfg.name} (no quantization)")
        return params, None
    if verbose:
        print(f"[serve] calibrating {cfg.name} to {qcfg.tag} "
              f"with {method}+{init} ...")
    t0 = time.time()
    calib = calibration_batches(data_cfg, 2, max(2, calib_samples // 2))
    calib = [{"tokens": jnp.asarray(b["tokens"][:, :-1])} for b in calib]
    params_fq, qmeta, _ = quantize_model(cfg, params, calib, qcfg,
                                         method=method, init=init, tcfg=tcfg)
    packed = pack_model(cfg, params_fq, qmeta, qcfg)
    report = quantized_memory_report(packed)
    if verbose:
        print(f"[serve] calibration done in {time.time()-t0:.1f}s; {report}")
    return packed, report


# per-(cfg, backend, act_bits, mesh, tp_shard) jit pairs: the serve-mesh
# path must hand every caller the SAME jitted steps (distinct-but-equal
# wrappers defeat jit's tracing cache — the PR 4 recompile class), and the
# memoized serve_mesh guarantees mesh identity so the key is cheap.
_SERVE_STEP_CACHE: dict = {}


def compile_serve_steps(cfg, *, kernel_backend=None, act_bits=None,
                        mesh=None, tp_shard: bool = False):
    """Jit-wrap the prefill/decode steps ONCE for a (backend, act_bits,
    mesh) serving configuration — memoized, so benchmarks and the repeated
    bench/CLI call sites all reuse one compiled pair per configuration
    (re-wrapping per call would retrace and recompile, and the timings
    would measure XLA, not serving).

    ``mesh`` must be single-pod: serving has no cross-pod path (the
    pipelined quantization walk is the only multi-pod consumer) — give
    each pod its own submesh via ``launch.mesh.pod_submeshes`` instead.
    ``tp_shard=True`` routes the steps through the tensor-parallel
    ServeSpec contract (shard_map over the mesh's ``model`` axis)."""
    validate_single_pod(mesh, "compile_serve_steps")
    key = (cfg, kernel_backend, act_bits, mesh, tp_shard)
    if key not in _SERVE_STEP_CACHE:
        _, prefill_step, decode_step = make_serve_steps(
            cfg, mesh, act_bits=act_bits, kernel_backend=kernel_backend,
            tp_shard=tp_shard)
        _SERVE_STEP_CACHE[key] = (
            jax.jit(prefill_step),
            jax.jit(decode_step, donate_argnums=cache_donate_argnums(1)))
    return _SERVE_STEP_CACHE[key]


# the +1 constant lives inside the compiled program instead of being
# device_put per decode step (transfer_guard-clean)
_inc1 = jax.jit(lambda p: p + 1)


def serve_requests(cfg, model, params, prompts, *, gen: int,
                   kernel_backend=None, act_bits=None, compiled=None,
                   collect_logits=True, max_seq=None, mesh=None,
                   tp_shard: bool = False) -> "ServeResult":
    """Prefill + lock-step batched decode (uniform lengths, fixed ``gen``).

    Returns a ``repro.launch.scheduler.ServeResult`` whose ``tokens``
    property is the (B, gen) token matrix and whose ``logits`` property is
    the (B, gen, V) stack of the prefill output plus each decode step's,
    so callers can gate backend parity on them (``collect_logits=False``
    drops them for timing-only runs).
    ``compiled``: a ``compile_serve_steps`` pair to reuse (built fresh
    otherwise).  Device->host transfers happen OUTSIDE the timed loop —
    the decode section times async step dispatch plus one final sync.
    ``max_seq`` overrides the cache width (default: exactly prompt+gen);
    the scheduler parity tests pass the scheduler's width so both runs
    reduce over identical cache extents."""
    from repro.launch.scheduler import ServeResult, _latency_stats
    B, prompt_len = prompts.shape
    if max_seq is None:
        max_seq = prompt_len + gen
    elif max_seq < prompt_len + gen:
        raise ValueError(f"max_seq {max_seq} < prompt+gen "
                         f"{prompt_len + gen}")
    pstep, dstep = compiled if compiled is not None else compile_serve_steps(
        cfg, kernel_backend=kernel_backend, act_bits=act_bits, mesh=mesh,
        tp_shard=tp_shard)

    # TP serving: commit params/cache to their ServeSpec placement ONCE,
    # off the timed loop — otherwise every jitted step dispatch reshards
    # the device-0 trees onto the mesh (an implicit device-to-device
    # transfer per step: slow, and rejected by the serving sanitizer)
    rep = None
    if tp_shard and mesh is not None:
        from repro.launch.sharding import ServeSpec
        tp_spec = ServeSpec.for_mesh(mesh, cfg)
        if tp_spec.active:
            plan = tp_spec.plan(params)
            params = tp_spec.place_params(params, plan)
            rep = tp_spec.replicated()

    cache = model.init_cache(B, max_seq)
    if rep is not None:
        cache = tp_spec.place_cache(model.cache_spec, cache, plan)
        toks_in = jax.device_put(prompts, rep)
    else:
        toks_in = jax.device_put(prompts)
    t0 = time.time()
    logits, cache = pstep(params, {"tokens": toks_in}, cache)
    logits.block_until_ready()   # reprolint: ok[host-sync] — prefill timing boundary
    t_prefill = time.time() - t0

    all_logits = [logits] if collect_logits else None
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    # host-built then explicitly placed / jit-incremented: eager jnp.full
    # and `pos + 1` each device_put a scalar constant per call, which the
    # serving sanitizer's transfer_guard rejects
    pos = (jax.device_put(np.full((B,), prompt_len, np.int32), rep)
           if rep is not None
           else jax.device_put(np.full((B,), prompt_len, np.int32)))
    toks = [tok]
    t0 = time.time()
    for _ in range(gen - 1):
        logits, cache = dstep(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = _inc1(pos)
        toks.append(tok)
        if collect_logits:
            all_logits.append(logits)
    tok.block_until_ready()   # reprolint: ok[host-sync] — closes the decode timing region
    t_decode = time.time() - t0
    # reprolint: ok[host-sync] — off-clock host fetch; both timing regions already closed
    tok_mat = np.stack([np.asarray(jax.device_get(t)) for t in toks], 1)
    # reprolint: ok[host-sync] — off-clock host fetch of the opt-in logits trace
    lg_mat = (np.stack([np.asarray(jax.device_get(a), np.float32)
                        for a in all_logits], 1)
              if collect_logits else None)                     # (B, gen, V)
    res = {b: {"tokens": tok_mat[b],
               "logits": None if lg_mat is None else lg_mat[b],
               "arrival": 0, "admit_step": 0, "finish_step": gen - 1,
               "latency_steps": gen - 1}
           for b in range(B)}
    cache_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(cache))
    return ServeResult(
        mode="uniform", store="dense", requests=res,
        slots=B, max_seq=max_seq, steps=gen - 1,
        useful_tokens=B * gen, decode_tokens=B * (gen - 1),
        prefill_secs=t_prefill, decode_secs=t_decode,
        prefill_tok_s=B * prompt_len / max(t_prefill, 1e-9),
        decode_tok_s=(B * (gen - 1) / max(t_decode, 1e-9)
                      if gen > 1 else 0.0),
        occupancy=1.0,
        latency_steps=_latency_stats([gen - 1] * B),
        cache_stats={"store": "dense", "cache_bytes": cache_bytes,
                     "slots": B, "max_seq": max_seq},
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="W4A16g32")
    ap.add_argument("--method", default="tesseraq",
                    choices=["tesseraq", "omniquant", "none"])
    ap.add_argument("--init", default="awq", choices=["awq", "rtn", "gptq"])
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"],
                    help="QTensor matmul dispatch for the serve steps")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=None,
                    help="serve through the continuous-batching scheduler "
                         "with this many slots over a seeded heterogeneous "
                         "workload (prompt lens up to --prompt-len, budgets "
                         "up to --gen); default: uniform lock-step loop")
    ap.add_argument("--store", default="dense", choices=["dense", "paged"],
                    help="KV cache store for --slots serving: dense per-slot "
                         "lanes, or the paged pool + page-table layout")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged pool size (default: dense-capacity parity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunk long prompts into this many tokens per "
                         "decode iteration (chunkable families only)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="copy-on-write sharing of full prompt-prefix pages "
                         "(paged store + chunked prefill only)")
    ap.add_argument("--tp", type=int, default=None,
                    help="serve-time tensor parallelism: shard packed "
                         "QTensor weights and KV heads over the 'model' "
                         "axis of launch.mesh.serve_mesh(tp=N) via the "
                         "ServeSpec contract; default: no mesh "
                         "(single-device serving)")
    ap.add_argument("--calib-samples", type=int, default=8)
    ap.add_argument("--par-iters", type=int, default=4)
    ap.add_argument("--par-steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    qcfg = parse_quant(args.quant, kernel_backend=args.backend)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.prompt_len,
                          global_batch=args.requests, seed=args.seed)
    tcfg = TesseraQConfig(par_iterations=args.par_iters,
                          steps_per_iteration=args.par_steps)
    served, _ = build_params(cfg, params, qcfg, data_cfg, method=args.method,
                             init=args.init, tcfg=tcfg,
                             calib_samples=args.calib_samples)

    act = qcfg.act_bits if args.method != "none" else None

    mesh = None
    if args.tp is not None:
        from repro.launch.mesh import serve_mesh
        mesh = serve_mesh(tp=args.tp)

    if args.slots is not None:
        # ---- scheduled serving (continuous batching) ------------------------
        from repro.launch.scheduler import make_workload, serve_scheduled
        if args.prompt_len < 1 or args.gen < 1:
            raise SystemExit("--slots needs --prompt-len and --gen >= 1")
        # clamp the plan ranges so small --prompt-len/--gen stay valid
        # (rng.integers(lo, hi+1) requires lo <= hi)
        reqs = make_workload(cfg.vocab_size, n_requests=args.requests,
                             seed=args.seed,
                             prompt_lens=(min(max(4, args.prompt_len // 4),
                                              args.prompt_len),
                                          args.prompt_len),
                             budgets=(min(2, args.gen), args.gen))
        sched = serve_scheduled(cfg, served, reqs, slots=args.slots,
                                kernel_backend=qcfg.kernel_backend,
                                act_bits=act, store=args.store,
                                page_size=args.page_size,
                                num_pages=args.num_pages,
                                prefill_chunk=args.prefill_chunk,
                                share_prefix=args.share_prefix,
                                mesh=mesh, tp_shard=mesh is not None)
        lat = sched.latency_steps
        dev = jax.devices()[0]
        print(f"[serve] scheduled {args.requests} requests over "
              f"{args.slots} slots in {sched.steps} decode steps "
              f"({sched.useful_tokens} useful tokens, occupancy "
              f"{sched.occupancy:.2f}, decode "
              f"{sched.decode_tok_s:.1f} tok/s over the host seconds "
              f"outside admissions, backend={args.backend}, "
              f"device {dev.platform}/{dev.device_kind})")
        print(f"[serve] latency (decode steps): mean {lat['mean']:.1f} "
              f"p50 {lat['p50']:.0f} p90 {lat['p90']:.0f} "
              f"p99 {lat['p99']:.0f}")
        cs = sched.cache_stats
        if sched.store == "paged":
            print(f"[serve] paged cache: {cs['cache_bytes'] / 1e6:.2f} MB, "
                  f"{cs['num_pages']} pages x {cs['page_size']} tokens, "
                  f"peak in use {cs['peak_pages_in_use']}, refused "
                  f"{cs['refused_admissions']}, shared-page hits "
                  f"{cs['shared_page_hits']}")
        else:
            print(f"[serve] dense cache: {cs['cache_bytes'] / 1e6:.2f} MB")
        for r in reqs[:4]:
            rr = sched.requests[r.rid]
            print(f"  req{r.rid}: plen={len(r.prompt)} "
                  f"budget={r.max_new_tokens} arrive@{r.arrival} "
                  f"admit@{rr['admit_step']} finish@{rr['finish_step']} -> "
                  f"{rr['tokens'][:8].tolist()}")
        return 0

    # ---- uniform lock-step serving ------------------------------------------
    corpus = SyntheticCorpus(data_cfg)
    prompts = corpus.batch(0)["tokens"][:, :args.prompt_len]
    stats = serve_requests(cfg, model, served, prompts, gen=args.gen,
                           kernel_backend=qcfg.kernel_backend, act_bits=act,
                           mesh=mesh, tp_shard=mesh is not None)
    B, gen = args.requests, args.gen
    dt = stats.prefill_secs + stats.decode_secs
    dev = jax.devices()[0]
    print(f"[serve] {B} requests x {gen} tokens in {dt:.2f}s "
          f"(prefill {stats.prefill_tok_s:.1f} tok/s, decode "
          f"{stats.decode_tok_s:.1f} tok/s, backend={args.backend}, "
          f"device {dev.platform}/{dev.device_kind})")
    print("[serve] sample generations (token ids):")
    toks = stats.tokens
    for b in range(min(B, 4)):
        print(f"  req{b}: {prompts[b][-8:].tolist()} -> "
              f"{toks[b][:12].tolist()}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
