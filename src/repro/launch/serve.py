"""Serving launcher: quantize (TesseraQ) then serve requests with packed
weights — the paper's deployment scenario (Table 8).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --quant W4A16g32 --requests 8 --prompt-len 32 --gen 16

``--method none`` skips quantization entirely and serves the plain FP
params (the fp16 baseline every Table 8 comparison is against);
``--backend pallas`` routes every QTensor matmul through the fused Pallas
dequant-matmul kernel instead of the XLA unpack path.

Requests are served by the slot scheduler
(``repro.launch.scheduler.serve_scheduled``), the one serve loop.  Without
``--slots`` the launcher serves ``--requests`` uniform requests (prompts of
``--prompt-len`` tokens, ``--gen`` tokens each, all arriving at once) over
as many slots; ``--slots N`` serves a seeded heterogeneous plan instead
(prompt lengths up to ``--prompt-len``, budgets up to ``--gen``, staggered
arrivals) over N slots.
"""
from __future__ import annotations

import argparse
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import pack_model, quantize_model, quantized_memory_report
from repro.core.qtensor import PACK_FACTOR
from repro.core.tesseraq import TesseraQConfig
from repro.data.pipeline import DataConfig, SyntheticCorpus, calibration_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.scheduler import Request, make_workload, serve_scheduled
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
                    help="serve a seeded heterogeneous workload (prompt "
                         "lens up to --prompt-len, budgets up to --gen) "
                         "over this many slots; default: --requests uniform "
                         "requests over --requests slots")
    ap.add_argument("--store", default="dense", choices=["dense", "paged"],
                    help="KV cache store: dense per-slot lanes, or the "
                         "paged pool + page-table layout")
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

    if args.prompt_len < 1 or args.gen < 1:
        raise SystemExit("--prompt-len and --gen must be >= 1")
    if args.slots is None:
        slots = args.requests
        prompts = SyntheticCorpus(data_cfg).batch(0)["tokens"]
        reqs = [Request(rid=b, prompt=prompts[b, :args.prompt_len],
                        max_new_tokens=args.gen)
                for b in range(args.requests)]
    else:
        slots = args.slots
        # clamp the plan ranges so small --prompt-len/--gen stay valid
        # (rng.integers(lo, hi+1) requires lo <= hi)
        reqs = make_workload(cfg.vocab_size, n_requests=args.requests,
                             seed=args.seed,
                             prompt_lens=(min(max(4, args.prompt_len // 4),
                                              args.prompt_len),
                                          args.prompt_len),
                             budgets=(min(2, args.gen), args.gen))
    res = serve_scheduled(cfg, served, reqs, slots=slots,
                          kernel_backend=qcfg.kernel_backend, act_bits=act,
                          store=args.store, page_size=args.page_size,
                          num_pages=args.num_pages,
                          prefill_chunk=args.prefill_chunk,
                          share_prefix=args.share_prefix,
                          mesh=mesh, tp_shard=mesh is not None)
    lat = res.latency_steps
    dev = jax.devices()[0]
    print(f"[serve] scheduled {args.requests} requests over {slots} slots "
          f"in {res.steps} decode steps ({res.useful_tokens} useful tokens, "
          f"occupancy {res.occupancy:.2f}, backend={args.backend}, "
          f"device {dev.platform}/{dev.device_kind})")
    print(f"[serve] latency (decode steps): mean {lat['mean']:.1f} "
          f"p50 {lat['p50']:.0f} p90 {lat['p90']:.0f} p99 {lat['p99']:.0f}")
    cs = res.cache_stats
    if res.store == "paged":
        print(f"[serve] paged cache: {cs['cache_bytes'] / 1e6:.2f} MB, "
              f"{cs['num_pages']} pages x {cs['page_size']} tokens, "
              f"peak in use {cs['peak_pages_in_use']}, refused "
              f"{cs['refused_admissions']}, shared-page hits "
              f"{cs['shared_page_hits']}")
    else:
        print(f"[serve] dense cache: {cs['cache_bytes'] / 1e6:.2f} MB")
    for r in reqs[:4]:
        rr = res.requests[r.rid]
        print(f"  req{r.rid}: plen={len(r.prompt)} "
              f"budget={r.max_new_tokens} arrive@{r.arrival} "
              f"admit@{rr['admit_step']} finish@{rr['finish_step']} -> "
              f"{rr['tokens'][:8].tolist()}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
