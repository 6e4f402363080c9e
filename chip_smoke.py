"""Bring-up check: quantize -> pack -> serve at the full width of
tinyllama-1.1b (22 layers, d_model 2048, 32/4 heads, d_ff 5632, vocab 32000)
on a TPU, in one process.

    python chip_smoke.py              # one chip: the whole main path
    python chip_smoke.py --chips 4    # the multi-chip paths only

One chip: ``quantize_model`` (TesseraQ, AWQ init, W2A16g128, device engine)
over all 22 blocks, ``pack_model``, then ``serve_scheduled`` with packed
weights on both kernel backends and both KV stores.  Checks, any of which
failing exits non-zero:

  * every block's reconstruction losses are finite;
  * the packed model (xla backend) and the fake-quant params give the same
    next-token loss on held-out tokens, within the bound of
    ``tests/test_tesseraq.py::test_pack_equals_fake_quant``;
  * pallas vs xla logits agree under ``eval.harness.parity_gate`` at the
    serve-path tolerances of ``tests/test_kernel_backend.py``, in that
    test's setting: float32 activations with full-precision matmuls, where
    the backends differ only by accumulation order (in bf16 every matmul
    output rounds to bf16, reassociation flips those roundings, and over 22
    layers that exceeds the gate: the bf16 figure is printed, not gated);
  * the dense and paged stores emit identical tokens on each backend;
  * the pallas decode step lowers to compiled Mosaic kernels
    (``tpu_custom_call``), not interpret mode.

Four chips (``--chips 4``): TP=4 serving on ``serve_mesh(tp=4)`` against
TP=1 on the same packed params (identical tokens, logits within the 5e-3
psum seam, which is documented for float32 — so this comparison serves the
model in float32), and the sharded reconstruction engine on a ("data",
"model") 2x2 mesh against the device engine for two blocks (hardened masks
and codes bit-identical).

Weights and calibration tokens are generated from ``--seed``; nothing is
read from disk.  Without a TPU the script exits non-zero before any model
work.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

# serve-path backend-parity tolerances (tests/test_kernel_backend.py)
PARITY_ATOL, PARITY_RTOL = 5e-2, 2e-2
# TP psum seam (tests/test_tp_serve.py, README "Tensor-parallel serving")
TP_ATOL = TP_RTOL = 5e-3
# packed vs fake-quant loss (tests/test_tesseraq.py::test_pack_equals_fake_quant)
PACK_LOSS_TOL = 0.02

QUANT = "W2A16g128"
CALIB_BATCHES, CALIB_BATCH, CALIB_LEN = 2, 4, 256    # 8 sequences x 256
PAR_ITERS, PAR_STEPS = 2, 10
N_REQUESTS, SLOTS = 16, 8
PROMPT_LENS, BUDGETS = (128, 256), (16, 64)
PAGE_SIZE = 16

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Phases:
    """Wall and compile seconds per phase, from JAX's monitoring events."""

    def __init__(self):
        self.name = None
        self.rows = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _row(self):
        return self.rows.setdefault(self.name, {"wall_s": 0.0,
                                                "compile_s": 0.0,
                                                "cache_hits": 0,
                                                "cache_misses": 0})

    def _dur(self, event, secs, **_):
        if self.name is not None and event in _COMPILE_EVENTS:
            self._row()["compile_s"] += secs

    def _event(self, event, **_):
        if self.name is None:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self._row()["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._row()["cache_misses"] += 1

    def run(self, name, fn, *args, **kw):
        self.name = name
        t0 = time.time()
        try:
            return fn(*args, **kw)
        finally:
            row = self._row()
            row["wall_s"] += time.time() - t0
            self.name = None
            print(f"[phase] {name}: wall {row['wall_s']:.1f}s, compile "
                  f"{row['compile_s']:.1f}s (persistent cache "
                  f"{row['cache_hits']} hits / {row['cache_misses']} misses)",
                  flush=True)


def check(ok: bool, what: str):
    print(f"[check] {'PASS' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int:
    return max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# shared pieces (sizes are read from the module constants at call time, so a
# CPU rehearsal can import this file and drive it at toy sizes)
# ---------------------------------------------------------------------------

def _data_cfg(cfg, seed: int):
    from repro.data.pipeline import DataConfig
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=CALIB_LEN + 1,
                      global_batch=CALIB_BATCH, seed=seed)


def calibration(cfg, seed: int):
    import jax.numpy as jnp

    from repro.data.pipeline import calibration_batches
    return [{"tokens": jnp.asarray(b["tokens"][:, :-1])}
            for b in calibration_batches(_data_cfg(cfg, seed), CALIB_BATCHES,
                                         CALIB_BATCH)]


def held_out(cfg, seed: int):
    """One (CALIB_BATCH, CALIB_LEN + 1) batch the calibration never saw."""
    import jax.numpy as jnp

    from repro.data.pipeline import eval_batches
    b = eval_batches(_data_cfg(cfg, seed), 1, CALIB_BATCH)[0]
    return {"tokens": jnp.asarray(b["tokens"])}


def requests(cfg, seed: int, n: int):
    """Seeded requests: prompt lengths from a small set (one prefill compile
    per distinct length), budgets uniform, two arrivals per decode step."""
    import numpy as np

    from repro.launch.scheduler import Request
    rng = np.random.default_rng(seed)
    out = []
    for rid in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        out.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=int(rng.integers(BUDGETS[0], BUDGETS[1] + 1)),
            arrival=rid // 2))
    return out


def width(reqs) -> int:
    need = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    return need + (-need) % PAGE_SIZE


def compare_streams(a, b, rids, *, atol, rtol):
    """Compare two serve runs of the same requests.

    Logits go through ``parity_gate`` over the steps each request saw with
    identical history: up to and including its first differing token.
    Random weights at full width give nearly tied top logits, and greedy
    decoding resolves a tie either way, so a differing token is a near-tie
    when, under both runs' logits at that step, the two chosen tokens are
    within the gate's tolerance of each other; past it the streams are no
    longer comparable.  ``untied`` counts divergences that are not."""
    import numpy as np

    from repro.eval.harness import parity_gate
    la, lb, diverged, untied = [], [], 0, 0
    for rid in rids:
        ta, tb = a[rid]["tokens"], b[rid]["tokens"]
        diff = np.flatnonzero(ta != tb)
        n = len(ta) if diff.size == 0 else int(diff[0]) + 1
        la.append(a[rid]["logits"][:n])
        lb.append(b[rid]["logits"][:n])
        if diff.size:
            diverged += 1
            s = n - 1
            for lg in (la[-1][s], lb[-1][s]):
                x, y = lg[ta[s]], lg[tb[s]]
                untied += int(abs(x - y) > atol + rtol * max(abs(x), abs(y)))
    gate = parity_gate(np.concatenate(la)[None], np.concatenate(lb)[None],
                       atol=atol, rtol=rtol)
    gate.update(requests_diverged=diverged, untied=untied)
    return gate


def serve_runs(cfg, packed, reqs, *, max_seq: int, phases: "Phases"):
    """The four scheduled runs: {xla, pallas} x {dense, paged}."""
    from repro.launch.scheduler import compile_sched_steps, serve_scheduled
    page_size = PAGE_SIZE
    out = {}
    for backend in ("xla", "pallas"):
        for store in ("dense", "paged"):
            paged = store == "paged"
            # on pallas the dense kernel walks the paged kernel's chunk grid
            # (decode chunk == page size), so both reduce in the same order
            steps = compile_sched_steps(
                cfg, max_seq=max_seq, kernel_backend=backend,
                page_size=page_size if paged else 0,
                decode_attn_chunk=(page_size if backend == "pallas"
                                   and not paged else 1 << 30))
            res = phases.run(f"serve {backend}/{store}", serve_scheduled,
                             cfg, packed, reqs, slots=SLOTS, max_seq=max_seq,
                             kernel_backend=backend, collect_logits=True,
                             compiled=steps, store=store, page_size=page_size)
            out[(backend, store)] = (res, steps)
    return out


def decode_hlo_has_kernels(cfg, model, steps, packed, *, slots: int,
                           max_seq: int) -> bool:
    """Lower the scheduler's decode step (no execution) and look for the
    Mosaic custom call that compiled Pallas kernels lower to."""
    import jax.numpy as jnp
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_seq))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    act = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    text = steps.decode.lower(packed, cache, vec, vec, act).as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def one_chip(cfg, seed: int, phases: Phases) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pack_model, quantize_model, quantized_memory_report
    from repro.core.tesseraq import TesseraQConfig
    from repro.launch.scheduler import serve_scheduled
    from repro.launch.serve import parse_quant
    from repro.models import get_model
    from repro.models.common import Ctx

    model = get_model(cfg)
    qcfg = parse_quant(QUANT)
    params = phases.run("init params", jax.jit(model.init_params),
                        jax.random.PRNGKey(seed))
    calib = calibration(cfg, seed)
    tcfg = TesseraQConfig(par_iterations=PAR_ITERS,
                          steps_per_iteration=PAR_STEPS, engine="device")
    print(f"[quantize] {cfg.name}: {cfg.num_layers} blocks, {QUANT}, "
          f"tesseraq+awq, {len(calib) * CALIB_BATCH} x {CALIB_LEN} calibration "
          f"tokens, PAR {PAR_ITERS} x {PAR_STEPS} steps", flush=True)
    params_fq, qmeta, report = phases.run(
        "quantize", quantize_model, cfg, params, calib, qcfg,
        method="tesseraq", init="awq", tcfg=tcfg)
    del params
    secs = [b["secs"] for b in report["blocks"]]
    print(f"[quantize] seconds per block: first {secs[0]:.2f}, median of "
          f"the rest {float(np.median(secs[1:] or secs)):.2f}, total "
          f"{sum(secs):.1f} over {len(secs)} blocks", flush=True)
    losses = [l["loss"] for b in report["blocks"] for l in b["log"]]
    losses += [b["recon_mse"] for b in report["blocks"]]
    check(len(report["blocks"]) == cfg.num_layers
          and bool(np.all(np.isfinite(losses))),
          f"{len(report['blocks'])} blocks reconstructed, all "
          f"{len(losses)} reconstruction losses finite "
          f"(last block recon mse {report['blocks'][-1]['recon_mse']:.3e})")

    packed = phases.run("pack", lambda: jax.block_until_ready(
        pack_model(cfg, params_fq, qmeta, qcfg)))
    del qmeta
    print(f"[pack] {quantized_memory_report(packed)}", flush=True)

    reqs = requests(cfg, seed, N_REQUESTS)
    max_seq = width(reqs)

    # packed (xla dequant) vs the fake-quant params, on the loss their
    # logits give.  Elementwise logits are not the measure: in bf16 the
    # packed path divides activations by the AWQ act_scale at serve time
    # while the fake-quant weights have it folded in, which rounds
    # differently (with RTN init the two are bit-identical).
    ctx = Ctx(kernel_backend="xla")
    loss = jax.jit(lambda p, b: model.loss_fn(p, b, ctx))
    batch = held_out(cfg, seed)
    l_fq = float(phases.run("loss fake-quant", loss, params_fq, batch))
    l_pk = float(phases.run("loss packed xla", loss, packed, batch))
    del params_fq
    check(abs(l_fq - l_pk) < PACK_LOSS_TOL,
          f"packed xla loss {l_pk:.5f} vs fake-quant loss {l_fq:.5f} on "
          f"held-out tokens (|d| < {PACK_LOSS_TOL})")

    runs = serve_runs(cfg, packed, reqs, max_seq=max_seq, phases=phases)
    rids = [r.rid for r in reqs]
    for backend in ("xla", "pallas"):
        dense, paged = runs[(backend, "dense")][0], runs[(backend, "paged")][0]
        same = all(np.array_equal(dense.requests[r]["tokens"],
                                  paged.requests[r]["tokens"]) for r in rids)
        check(same, f"{backend}: dense and paged stores emit identical "
              f"tokens ({dense.useful_tokens} tokens, {len(rids)} requests)")
    gate = compare_streams(runs[("xla", "dense")][0].requests,
                           runs[("pallas", "dense")][0].requests, rids,
                           atol=PARITY_ATOL, rtol=PARITY_RTOL)
    print(f"[info] bf16 pallas vs xla logits (not gated): max |d| "
          f"{gate['max_abs_diff']:.3e} over {gate['steps_compared']} steps, "
          f"{gate['requests_diverged']} of {len(rids)} requests picked a "
          f"different greedy token before their budget", flush=True)

    f32 = cfg.replace(dtype="float32")
    packed32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        packed)
    f32_runs = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("xla", "pallas"):
            f32_runs[backend] = phases.run(
                f"serve {backend}/dense f32", serve_scheduled, f32,
                packed32, reqs, slots=SLOTS, max_seq=max_seq,
                kernel_backend=backend, collect_logits=True)
    del packed32
    gate = compare_streams(f32_runs["xla"].requests,
                           f32_runs["pallas"].requests, rids,
                           atol=PARITY_ATOL, rtol=PARITY_RTOL)
    check(gate["ok"] and not gate["untied"],
          f"float32 pallas vs xla logits: max |d| "
          f"{gate['max_abs_diff']:.3e} over {gate['steps_compared']} steps; "
          f"tokens equal but for {gate['requests_diverged']} of {len(rids)} "
          f"requests, each at a near-tie")
    steps = runs[("pallas", "dense")][1]
    check(decode_hlo_has_kernels(cfg, steps.model, steps, packed,
                                 slots=SLOTS, max_seq=max_seq),
          "pallas decode step lowers to tpu_custom_call (compiled Mosaic)")

    # serving speed with the per-step logits fetch off (compiled steps reused)
    for backend in ("xla", "pallas"):
        steps = runs[(backend, "dense")][1]
        res = phases.run(f"serve {backend}/dense timed", serve_scheduled, cfg,
                         packed, reqs, slots=SLOTS, max_seq=max_seq,
                         kernel_backend=backend, compiled=steps)
        # host seconds: an admission's first-token sync also waits for the
        # decode steps dispatched ahead of it, so "prefill" counts those too
        print(f"[serve] {backend}/dense: decode {res.decode_tok_s:.1f} tok/s "
              f"over host seconds outside admissions, prompt "
              f"{res.prefill_tok_s:.1f} tok/s over host seconds inside "
              f"admissions, {res.steps} decode steps over {SLOTS} slots, "
              f"occupancy {res.occupancy:.2f}", flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(cfg, seed: int, phases: Phases) -> None:
    import numpy as np

    from repro.configs.base import QuantConfig
    from repro.core import pack_model, quantize_model
    from repro.core import tesseraq as TQ
    from repro.core.blocks import build_stages
    from repro.core.rtn import quantize_block_rtn
    from repro.launch.mesh import make_mesh, serve_mesh
    from repro.launch.scheduler import serve_scheduled
    from repro.launch.serve import parse_quant
    from repro.models import get_model

    n = len(jax.devices())
    check(n == 4, f"{n} devices visible (want 4)")
    model = get_model(cfg)
    qcfg = parse_quant(QUANT)
    params = phases.run("init params", jax.jit(model.init_params),
                        jax.random.PRNGKey(seed))
    calib = calibration(cfg, seed)

    # -- TP=4 serving vs TP=1 on the same packed params ----------------------
    # float32 at full matmul precision, so the logits differ only by the
    # in-split psum's reassociation (the seam TP_ATOL documents) and the
    # bf16 KV cache's rounding of it, not by bf16 matmuls
    f32 = cfg.replace(dtype="float32")
    pq, qmeta, _ = phases.run(
        "rtn quantize f32", quantize_model, f32,
        jax.tree_util.tree_map(lambda a: a.astype("float32"), params),
        calib, qcfg, method="none", init="rtn")
    packed = pack_model(f32, pq, qmeta, qcfg)
    del pq, qmeta
    reqs = requests(cfg, seed, SLOTS)
    max_seq = width(reqs)
    runs = {}
    with jax.default_matmul_precision("highest"):
        for tp in (1, 4):
            mesh = serve_mesh(tp=tp) if tp > 1 else None
            runs[tp] = phases.run(
                f"serve pallas tp={tp}", serve_scheduled, f32, packed, reqs,
                slots=SLOTS, max_seq=max_seq, kernel_backend="pallas",
                collect_logits=True, mesh=mesh, tp_shard=mesh is not None)
    rids = [r.rid for r in reqs]
    gate = compare_streams(runs[1].requests, runs[4].requests, rids,
                           atol=TP_ATOL, rtol=TP_RTOL)
    # TP's contract is stricter than the backends': identical tokens, no
    # near-tie allowance
    check(gate["ok"] and not gate["requests_diverged"],
          f"TP=4 vs TP=1: logits max |d| {gate['max_abs_diff']:.3e} over "
          f"{gate['steps_compared']} steps; tokens identical "
          f"({runs[1].useful_tokens} tokens, {len(rids)} requests; "
          f"{gate['requests_diverged']} diverged)")
    del packed

    # -- sharded engine on a 2x2 mesh vs the device engine, two blocks -------
    stage = build_stages(cfg)[0]
    X = jax.numpy.concatenate(
        [stage.init_x(params, b, {}) for b in calib], 0)
    mesh = make_mesh((2, 2))
    rq = QuantConfig(bits=qcfg.bits, group_size=qcfg.group_size)
    apply = jax.jit(stage.apply)
    verdicts = []
    for i in range(2):
        bp = stage.get_block(params, i)
        Y = apply(bp, X, None)
        _, meta0 = quantize_block_rtn(bp, rq)
        metas = {}
        for engine, m in (("device", None), ("sharded", mesh)):
            tcfg = TQ.TesseraQConfig(par_iterations=PAR_ITERS,
                                     steps_per_iteration=PAR_STEPS,
                                     engine=engine, mesh=m)
            _, metas[engine] = phases.run(
                f"block {i} {engine} engine", TQ.reconstruct_block,
                stage.apply, bp, X, Y, None, dict(meta0), rq, tcfg)
        differ = {k: sum(int(np.sum(np.asarray(metas["device"][p][k])
                                     != np.asarray(metas["sharded"][p][k])))
                         for p in metas["device"])
                  for k in ("hard", "codes")}
        total = sum(int(np.size(metas["device"][p]["codes"]))
                    for p in metas["device"])
        # both blocks run before the verdict, so a failure reports both
        print(f"[info] block {i}: {differ['hard']} masks and "
              f"{differ['codes']} codes of {total} differ", flush=True)
        verdicts.append(not any(differ.values()))
        X = Y
    check(all(verdicts), "sharded engine on a 2x2 (data, model) mesh "
          "reproduces the device engine's hardened masks and codes for "
          f"both blocks (per block: {verdicts})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the whole main path on one chip; 4: only the "
                         "multi-chip paths against their one-chip runs")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the platform first: never fall back to the CPU
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {info['platform']}",
              file=sys.stderr)
        return 1
    print(f"[device] {info['kind']} x {info['count']} "
          f"(jax {jax.__version__})", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[compile cache] {enable_compile_cache()}", flush=True)
    phases = Phases()
    cfg = get_config("tinyllama-1.1b")
    t0 = time.time()
    (one_chip if args.chips == 1 else four_chips)(cfg, args.seed, phases)
    print(f"[done] {time.time() - t0:.1f}s; compile "
          f"{sum(r['compile_s'] for r in phases.rows.values()):.1f}s; peak "
          f"device memory {peak_bytes() / 2**30:.2f} GiB", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
