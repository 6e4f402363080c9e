"""Reconstruction-engine speed benchmark on one block of the reduced
tinyllama config, across the inner-loop implementations:

  * ``legacy``    — the pre-engine path (jitted grad + EAGER per-leaf Adam,
                    per-step host batch gather): the baseline the device
                    engine's >= 3x criterion is against;
  * ``reference`` — host loop with the fused jitted (grad+Adam) step: the
                    bit-for-bit parity oracle for the device engine;
  * ``device``    — the scanned on-device engine;
  * ``sharded``   — the device engine's scanned step shard_mapped over a
                    data-parallel mesh with batch-sharded calibration
                    streams and the hierarchical chunked gradient reduction
                    (compared only when >1 device is visible, e.g. under
                    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

    PYTHONPATH=src python -m benchmarks.recon_speed [--dryrun] [--json PATH]

Reports, per engine:
  * steady-state steps/sec over the full PAR loop (a warmup run through the
    same per-stage cache pays each path's one-time compilation — including
    BOTH PAR-iteration entry layouts, fresh single-device state and
    committed loop-carry state — exactly as ``quantize_model`` amortizes it
    over a stage's blocks);
  * blocking device->host reads per PAR iteration (via the
    ``recon_engine.host_read`` counter) — the device engine's contract is
    <= 1, and that one is the optional log line.

With multiple devices it additionally runs:
  * the sharded-vs-device throughput comparison at a chunking-exercising
    batch size (4x the DP degree, so each device reduces several gradient
    lanes locally before the one fused partial exchange);
  * a per-device calibration-stream memory measurement (batch-sharded
    streams must hold ~1/D of the replicated bytes per device);
  * a three-way parity gate on identical inputs at a PINNED calibration
    horizon (K=3, T=15 — independent of the perf-run scale): sharded ==
    device == reference on the discrete artifacts (hardened mask + packed
    codes, bit-for-bit) with folded scales within 1e-5.  XLA's per-program
    compilation choices inject ~1-ulp lane noise into the continuous state
    at some batch widths/horizons, which only the scales see; the discrete
    deployment artifact absorbs it (``tests/test_recon_engine.py`` pins
    full bit-exactness, scales included, at the unit-test scales).

With a model axis available (even device counts) it also runs the
``tp_vs_device`` parity gate — the sharded engine on a ("data","model")
mesh, rounding variables and Adam state TP-sharded per the ParamSpec
contract, must still match the device engine bit-for-bit — and with >= 8
devices the ``pipeline_efficiency`` gate: a pod-pipelined
``quantize_model`` walk of llama3-405b-smoke on a ("pod","data","model")
mesh whose cross-pod capture prefetch must hide >= 70% of the target
forwards behind reconstruction.

Every gate lands in ``BENCH_recon.json`` under ``gates`` as an explicit
``{name, threshold, measured, ok, cmp}`` record (plus the legacy ``checks``
map), so a regression can never ship green without leaving a paper trail:
the run FAILS (non-zero exit) if any applicable gate fails.  In the full
(non ``--dryrun``) configuration that includes ``sharded_vs_device >= 1.0``
— a data-parallel engine that loses to one device is a perf bug, not a
footnote.

``--dryrun`` shrinks the step counts so the script doubles as a CI smoke
test (`make bench-smoke`); the throughput gates only run in the full
configuration (tiny dryrun step counts measure dispatch overhead, not
steady state), parity and memory gates always run.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (emit, gate as _gate, run_sanitized,
                               sanitizer_gate)
from repro.configs import get_reduced_config
from repro.configs.base import QuantConfig
from repro.core import recon_engine as RE
from repro.core import tesseraq as TQ
from repro.core.blocks import build_stages
from repro.core.rtn import quantize_block_rtn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_size, make_data_mesh, make_mesh, tp_size
from repro.models import get_model


def make_problem(n_samples=8, seq=24):
    cfg = get_reduced_config("tinyllama-1.1b")
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (n_samples, seq)))
    stage = build_stages(cfg)[0]
    X = np.asarray(stage.init_x(params, {"tokens": tokens}, {}))
    bp = stage.get_block(params, 0)
    Y = np.asarray(jax.jit(stage.apply)(bp, jnp.asarray(X), None))
    return stage.apply, bp, X, Y


def run_engine(engine, apply, bp, X, Y, qmeta, qcfg, tcfg, *, with_log,
               cache):
    log = [] if with_log else None
    RE.reset_sync_count()
    # the legacy baseline IS the pre-contract loop (eager per-leaf Adam,
    # per-step host gathers) — guarding it would measure the guard, not
    # the baseline; every shipping engine runs under the sanitizer
    section = (lambda f: f()) if engine == "legacy" else run_sanitized
    t0 = time.time()
    _, meta = section(lambda: TQ.reconstruct_block(
        apply, bp, X, Y, None, dict(qmeta), qcfg, tcfg, log=log,
        cache=cache))
    elapsed = time.time() - t0
    K = tcfg.par_iterations
    steps = K * tcfg.steps_per_iteration
    return {"steps_per_sec": steps / elapsed, "elapsed": elapsed,
            "syncs_per_iter": RE.sync_count() / K}, meta


def bench_engine(engine, apply, bp, X, Y, qmeta, qcfg, *, K, T, bs):
    """Warmup through a per-stage cache (pays compilation once, as the
    pipeline amortizes it over a stage's blocks), then a timed run.

    The warmup runs TWO PAR iterations: iteration 0 enters the jitted loop
    with freshly-built (single-device) state, iteration 1 with the previous
    dispatch's committed outputs — two different input layouts, two
    compilation cache entries, both of which the timed run must hit."""
    tcfg = TQ.TesseraQConfig(par_iterations=K, steps_per_iteration=T,
                             batch_size=bs, engine=engine)
    warm = TQ.TesseraQConfig(par_iterations=2, steps_per_iteration=T,
                             batch_size=bs, engine=engine)
    cache = {}
    run_engine(engine, apply, bp, X, Y, qmeta, qcfg, warm, with_log=True,
               cache=cache)
    return run_engine(engine, apply, bp, X, Y, qmeta, qcfg, tcfg,
                      with_log=True, cache=cache)


def _meta_parity(a, b):
    """Discrete-artifact parity (hardened mask + codes, bit-for-bit) and
    scale agreement (rtol 1e-5 — compiler-level lane noise can touch the
    continuous state; the unit tests pin scales exactly at their scales)
    between two engines' qmeta."""
    for p in a:
        if not np.array_equal(np.asarray(a[p]["codes"]),
                              np.asarray(b[p]["codes"])):
            return False, f"codes diverged at {p}"
        if not np.array_equal(np.asarray(a[p]["hard"]),
                              np.asarray(b[p]["hard"])):
            return False, f"hardened mask diverged at {p}"
        sa = np.asarray(a[p]["scale"], np.float32)
        sb = np.asarray(b[p]["scale"], np.float32)
        if not np.allclose(sa, sb, rtol=1e-5):
            return False, f"folded scale drifted beyond 1e-5 at {p}"
    return True, "ok"


def stream_bytes_per_device(plan: "RE.BatchPlan") -> int:
    """Largest per-device share of the staged calibration streams."""
    per: dict = {}
    for arr in (plan.X, plan.Y, plan.aux):
        if arr is None:
            continue
        for s in arr.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return max(per.values())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny step counts, no throughput gates (CI smoke)")
    ap.add_argument("--par-k", type=int, default=None)
    ap.add_argument("--steps-t", type=int, default=None)
    ap.add_argument("--json", default="BENCH_recon.json",
                    help="machine-readable results artifact path")
    args = ap.parse_args(argv)

    K = args.par_k or (2 if args.dryrun else 4)
    T = args.steps_t or (4 if args.dryrun else 60)
    n_dev = len(jax.devices())

    # the calibration pool must fill the sharded section's chunking-
    # exercising minibatch (bs = 4x the DP degree) on multi-device hosts
    apply, bp, X, Y = make_problem(n_samples=max(8, 4 * n_dev))
    qcfg = QuantConfig(bits=2, group_size=32)
    _, qmeta = quantize_block_rtn(bp, qcfg)

    out = {"dryrun": args.dryrun, "n_devices": n_dev, "par_k": K,
           "steps_t": T, "engines": {}, "speedups": {}, "checks": {},
           "gates": []}

    results = {}
    for engine in ("legacy", "reference", "device"):
        r, _ = bench_engine(engine, apply, bp, X, Y, qmeta, qcfg,
                            K=K, T=T, bs=4)
        results[engine] = r
        out["engines"][engine] = r
        emit("recon_speed", engine, "steps_per_sec",
             f"{r['steps_per_sec']:.1f}", r["elapsed"] * 1e6)
        emit("recon_speed", engine, "host_syncs_per_par_iter",
             f"{r['syncs_per_iter']:.2f}")

    dev = results["device"]["steps_per_sec"]
    speedup_legacy = dev / results["legacy"]["steps_per_sec"]
    speedup_ref = dev / results["reference"]["steps_per_sec"]
    out["speedups"]["device_vs_legacy"] = speedup_legacy
    out["speedups"]["device_vs_reference"] = speedup_ref
    emit("recon_speed", "device_vs_legacy", "speedup",
         f"{speedup_legacy:.2f}")
    emit("recon_speed", "device_vs_reference", "speedup",
         f"{speedup_ref:.2f}")

    ok_all = True
    sharded_ok = n_dev > 1
    if sharded_ok:
        mesh = make_data_mesh()
        dp = dp_size(mesh)
        bs = min(4 * dp, X.shape[0])
        if RE.grad_chunk_count(bs, X.shape[0]) % dp:
            # e.g. a forced 6-way host platform: the canonical chunk count
            # gcd(bs, pool, CANONICAL_LANE_CHUNKS) cannot absorb this DP
            # degree — record why and still emit the artifact instead of
            # dying with a traceback and no JSON
            out["sharded_skipped"] = (
                f"DP degree {dp} does not divide the canonical chunk "
                f"count {RE.grad_chunk_count(bs, X.shape[0])} "
                f"(bs={bs}, pool={X.shape[0]}, "
                f"cap={RE.CANONICAL_LANE_CHUNKS})")
            print(f"sharded section skipped: {out['sharded_skipped']}")
            sharded_ok = False
    if sharded_ok:
        # sharded-vs-device perf comparison at a batch size that exercises
        # the hierarchical reduction: several lanes per device reduce
        # locally, only the per-shard chunk partials cross the interconnect
        out["sharded_batch_size"] = bs
        out["grad_chunks"] = RE.grad_chunk_count(bs, X.shape[0])
        for engine in ("device", "sharded"):
            r, _ = bench_engine(engine, apply, bp, X, Y, qmeta,
                                qcfg, K=K, T=T, bs=bs)
            out["engines"][f"{engine}_bs{bs}"] = r
            emit("recon_speed", f"{engine}_bs{bs}", "steps_per_sec",
                 f"{r['steps_per_sec']:.1f}", r["elapsed"] * 1e6)
        sharded_vs_dev = (out["engines"][f"sharded_bs{bs}"]["steps_per_sec"]
                          / out["engines"][f"device_bs{bs}"]["steps_per_sec"])
        out["speedups"]["sharded_vs_device"] = sharded_vs_dev
        emit("recon_speed", "sharded_vs_device", "speedup",
             f"{sharded_vs_dev:.2f}")

        # per-device calibration-stream memory: batch-sharded streams hold
        # ~1/D of the bytes a replicated pool would pin on every device.
        # The replicated baseline is computed on host (staging a second
        # device copy of the pool just to read .nbytes would double the
        # bench's footprint): X at its own dtype, Y promoted to float32
        # exactly as stage_calibration stores it.
        plan_sh = RE.stage_plan(X, Y, batch_size=bs, total_steps=1,
                                mesh=mesh)
        rep_bytes = int(np.asarray(X).nbytes + np.asarray(Y).size * 4)
        sh_bytes = stream_bytes_per_device(plan_sh)
        mem_reduction = rep_bytes / max(sh_bytes, 1)
        out["calibration_stream"] = {
            "replicated_bytes_per_device": rep_bytes,
            "sharded_bytes_per_device": sh_bytes,
            "reduction": mem_reduction}
        emit("recon_speed", "stream_mem_reduction", "x",
             f"{mem_reduction:.2f}")
        ok_all &= _gate(out, "stream_shard_reduction",
                        threshold=0.9 * dp, measured=mem_reduction,
                        ok=mem_reduction >= 0.9 * dp, cmp=">=")

        # three-way parity gate at the PINNED horizon (decoupled from the
        # perf-run scale: the determinism contract is a correctness gate
        # with its own calibration length; no warmup — only the metas
        # matter here, not steady-state timing)
        PK, PT = 3, 15
        metas = {}
        cache = {}
        for engine in ("reference", "device", "sharded"):
            tcfg = TQ.TesseraQConfig(par_iterations=PK,
                                     steps_per_iteration=PT,
                                     batch_size=bs, engine=engine)
            _, metas[engine] = run_engine(engine, apply, bp, X, Y, qmeta,
                                          qcfg, tcfg, with_log=False,
                                          cache=cache)
        ok_sd, why_sd = _meta_parity(metas["device"], metas["sharded"])
        ok_dr, why_dr = _meta_parity(metas["reference"], metas["device"])
        out["checks"]["sharded_eq_device"] = {"ok": ok_sd, "why": why_sd,
                                              "par_k": PK, "steps_t": PT}
        out["checks"]["device_eq_reference"] = {"ok": ok_dr, "why": why_dr}
        print(f"check: sharded == device (mask+codes bit-for-bit, "
              f"K={PK} T={PT}): {'PASS' if ok_sd else 'FAIL'} ({why_sd})")
        print(f"check: device == reference (mask+codes bit-for-bit): "
              f"{'PASS' if ok_dr else 'FAIL'} ({why_dr})")
        ok_all &= _gate(out, "three_way_parity", threshold=1.0,
                        measured=float(ok_sd and ok_dr),
                        ok=ok_sd and ok_dr, cmp=">=")

        if not args.dryrun:
            ok_all &= _gate(out, "sharded_vs_device_throughput",
                            threshold=1.0, measured=sharded_vs_dev,
                            ok=sharded_vs_dev >= 1.0, cmp=">=")

    # tensor-parallel parity gate: the sharded engine on a ("data","model")
    # mesh (rounding/DST variables, weights and Adam state sharded per the
    # launch.sharding.ParamSpec contract) must reproduce the device
    # engine's hardened masks and packed codes bit-for-bit, folded scales
    # within 1e-5 — the device-count-invariance contract extended to TP
    if n_dev >= 2 and n_dev % 2 == 0:
        tp = 4 if n_dev % 8 == 0 else 2
        mesh_tp = make_mesh((n_dev // tp, tp))
        dp_tp = dp_size(mesh_tp)
        bs_tp = max(dp_tp, min(4 * dp_tp, X.shape[0]))
        if RE.grad_chunk_count(bs_tp, X.shape[0]) % dp_tp:
            out["tp_skipped"] = (
                f"DP degree {dp_tp} does not divide the canonical chunk "
                f"count at bs={bs_tp}, pool={X.shape[0]}")
            print(f"tp section skipped: {out['tp_skipped']}")
        else:
            out["tp_mesh"] = {"data": dp_tp, "model": tp_size(mesh_tp)}
            PK, PT = 3, 15
            metas_tp = {}
            cache_tp = {}
            for engine, m_ in (("device", None), ("sharded", mesh_tp)):
                tcfg = TQ.TesseraQConfig(par_iterations=PK,
                                         steps_per_iteration=PT,
                                         batch_size=bs_tp, engine=engine,
                                         mesh=m_)
                _, metas_tp[engine] = run_engine(
                    engine, apply, bp, X, Y, qmeta, qcfg, tcfg,
                    with_log=False, cache=cache_tp)
            ok_tp, why_tp = _meta_parity(metas_tp["device"],
                                         metas_tp["sharded"])
            out["checks"]["tp_eq_device"] = {
                "ok": ok_tp, "why": why_tp, "par_k": PK, "steps_t": PT,
                "dp": dp_tp, "tp": tp_size(mesh_tp)}
            print(f"check: TP-sharded (dp={dp_tp}, tp={tp_size(mesh_tp)}) "
                  f"== device (mask+codes bit-for-bit, K={PK} T={PT}): "
                  f"{'PASS' if ok_tp else 'FAIL'} ({why_tp})")
            ok_all &= _gate(out, "tp_vs_device", threshold=1.0,
                            measured=float(ok_tp), ok=ok_tp, cmp=">=")

    # pod-pipelined block walk: quantize the llama3-405b-smoke config on a
    # ("pod","data","model") mesh and gate the pipeline's steady-state
    # efficiency (reconstruction time over reconstruction + residual
    # prefetch wait) — the cross-pod capture prefetch must actually hide
    # the target forwards, not serialize behind them
    if n_dev >= 8 and n_dev % 8 == 0:
        from repro.configs import get_reduced_config as _grc
        from repro.core.pipeline import quantize_model
        pcfg = _grc("llama3-405b")
        pm = get_model(pcfg)
        pparams = pm.init_params(jax.random.PRNGKey(0))
        prng = np.random.default_rng(0)
        pbatches = [{"tokens": jnp.asarray(
            prng.integers(0, pcfg.vocab_size, (8, 16)))}]
        mesh3 = make_mesh((2, 2, 2))
        ptcfg = TQ.TesseraQConfig(
            par_iterations=K, steps_per_iteration=T, batch_size=4,
            engine="sharded", mesh=mesh3)
        t0 = time.time()
        _, _, prep = quantize_model(
            pcfg, pparams, pbatches, qcfg, method="tesseraq",
            init="rtn", tcfg=ptcfg)
        pl = prep["pipeline"]
        out["pipeline"] = dict(pl)
        emit("recon_speed", "pod_walk", "wall_s",
             f"{time.time() - t0:.1f}")
        emit("recon_speed", "pod_walk", "efficiency",
             "n/a" if pl["efficiency"] is None
             else f"{pl['efficiency']:.3f}")
        eff = 1.0 if pl["efficiency"] is None else pl["efficiency"]
        ok_all &= _gate(out, "pipeline_efficiency", threshold=0.7,
                        measured=eff, ok=eff >= 0.7, cmp=">=")

    # every timed reconstruction above ran under the transfer guard
    ok_all &= sanitizer_gate(out)

    ok_sync = results["device"]["syncs_per_iter"] <= 1.0
    out["checks"]["device_host_syncs"] = {
        "ok": ok_sync, "per_iter": results["device"]["syncs_per_iter"]}
    ok_all &= _gate(out, "device_host_syncs", threshold=1.0,
                    measured=results["device"]["syncs_per_iter"],
                    ok=ok_sync, cmp="<=")

    if not args.dryrun:
        ok_speed = speedup_legacy >= 3.0
        out["checks"]["device_3x_legacy"] = {"ok": ok_speed,
                                             "speedup": speedup_legacy}
        ok_all &= _gate(out, "device_3x_legacy", threshold=3.0,
                        measured=speedup_legacy, ok=ok_speed, cmp=">=")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")

    if not ok_all:
        raise SystemExit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
