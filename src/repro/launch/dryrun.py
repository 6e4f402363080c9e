import os
# reprolint: ok[env-read] — intentional WRITE that must run before jax's first import locks the device count
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers, compiles,
fits, and emit the roofline terms — without real hardware.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single [--quant W2A16g128] [--out f.json]

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init); it is why this module is only ever imported in
its own process.

Cost accounting: ``cost_analysis`` counts a lax.scan body once, so the full
(scan-over-layers) program proves compile + memory_analysis while
FLOPs/bytes/collective totals come from DEPTH DIFFERENCING — the same step
is re-lowered *unrolled* at two small depths d1 < d2 with identical
shardings/caches/quantized weights:

    per_layer = (cost(d2) - cost(d1)) / (d2 - d1)
    total     = cost(d1) + (L - d1) * per_layer

Inner chunk scans are widened to one trip (attn_chunk = seq) in the
depth-diff programs so attention FLOPs are fully counted (the chunked and
full forms touch identical total bytes).
"""

import argparse
import json
import sys
import time

import jax

from repro.configs import SHAPES_BY_NAME, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import hlo_stats
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.serve import parse_quant
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   param_shardings)
from repro.launch.steps import (jit_train_step, make_serve_steps,
                                make_train_harness, prefill_input_specs,
                                quantize_param_struct, serve_input_specs,
                                train_input_specs)
from repro.models import get_model


def mem_dict(compiled):
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_hbm_per_device": (ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes
                                - ma.alias_size_in_bytes),
    }


def _lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, qcfg, *,
                attn_chunk, microbatches=1, seq_parallel=False,
                grad_compression=False, serve_sharding="tp",
                attn_seq_parallel=False, kv_bits=None):
    """Lower + compile one step program for ``cfg`` under ``mesh``."""
    model = get_model(cfg)
    params_struct = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    act_over = {"seq": ("model",)} if attn_seq_parallel else None
    with mesh:
        if shape.kind == "train":
            harness = make_train_harness(cfg, mesh, attn_chunk=attn_chunk,
                                         microbatches=microbatches,
                                         seq_parallel=seq_parallel,
                                         grad_compression=grad_compression,
                                         extra_overrides=act_over)
            batch_struct = train_input_specs(cfg, shape)
            step, _ = jit_train_step(harness, mesh, params_struct,
                                     batch_struct)
            opt_struct = jax.eval_shape(harness.init_opt, params_struct)
            return step.lower(params_struct, opt_struct,
                              batch_struct).compile()

        if qcfg is not None:
            params_struct = quantize_param_struct(params_struct, cfg, qcfg)
        _, prefill_step, decode_step = make_serve_steps(
            cfg, mesh, act_bits=qcfg.act_bits if qcfg else None,
            attn_chunk=attn_chunk, extra_overrides=act_over,
            kv_bits=kv_bits)
        overrides = {"fsdp": ()} if serve_sharding == "tp" else None
        pspec = param_shardings(mesh, params_struct, cfg, overrides)
        if shape.kind == "prefill":
            ins = prefill_input_specs(cfg, shape)
            cspec = cache_shardings(mesh, ins["cache"], cfg)
            bspec = batch_shardings(mesh, ins["batch"])
            lowered = jax.jit(
                prefill_step, in_shardings=(pspec, bspec, cspec)).lower(
                    params_struct, ins["batch"], ins["cache"])
        else:
            ins = serve_input_specs(cfg, shape, kv_bits=kv_bits)
            cspec = cache_shardings(mesh, ins["cache"], cfg)
            tspec = batch_shardings(mesh, {"t": ins["tokens"],
                                           "p": ins["pos"]})
            lowered = jax.jit(
                decode_step,
                in_shardings=(pspec, cspec, tspec["t"], tspec["p"]),
                # reprolint: ok[donation-guard] — AOT lowering only, never executed; aliasing feeds memory_analysis
                donate_argnums=(1,)).lower(
                    params_struct, ins["cache"], ins["tokens"], ins["pos"])
        return lowered.compile()


def _depth_cfg(cfg: ModelConfig, depth_mult: int) -> ModelConfig:
    """Depth-reduced unrolled config for differencing."""
    if cfg.family == "hybrid":
        d = cfg.attn_every * depth_mult
        return cfg.replace(num_layers=d, unroll_layers=True)
    kw = {"num_layers": depth_mult, "unroll_layers": True}
    if cfg.family == "encdec":
        kw["encoder_layers"] = depth_mult
    return cfg.replace(**kw)


def run_cell(arch: str, shape_name: str, mesh_kind: str, quant: str = "",
             attn_chunk: int = 512, block_correction: bool = True,
             remat=None, verbose: bool = True, microbatches: int = 1,
             seq_parallel: bool = False, grad_compression: bool = False,
             serve_sharding: str = "tp", attn_seq_parallel: bool = False,
             diff_full_chunk: bool = True, kv_bits=None):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = cfg.shape_valid(shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "why": why}

    if mesh_kind in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    else:
        mesh = make_mesh(tuple(int(x) for x in mesh_kind.split(",")))
    chips = mesh.size
    qcfg = parse_quant(quant) if quant and quant != "none" else None
    opts = dict(attn_chunk=attn_chunk, microbatches=microbatches,
                seq_parallel=seq_parallel, grad_compression=grad_compression,
                serve_sharding=serve_sharding,
                attn_seq_parallel=attn_seq_parallel, kv_bits=kv_bits)

    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "chips": chips, "quant": quant or "fp16",
              "kind": shape.kind, "status": "ok", "opts": dict(opts)}

    group = mesh.shape.get("model", chips)
    t0 = time.time()
    compiled = _lower_cell(cfg, shape, mesh, qcfg, **opts)
    result["compile_secs"] = time.time() - t0
    result["memory"] = mem_dict(compiled)
    whole = hlo_stats.cost_terms(compiled, compiled.as_text(), chips, group)
    result["whole_program"] = {k: v for k, v in whole.items()
                               if k != "coll_detail"}
    result["collectives"] = whole["coll_detail"]

    # ---- depth differencing -------------------------------------------------
    eff_L = cfg.num_layers
    total = whole
    if block_correction:
        try:
            o1 = dict(opts)
            if diff_full_chunk:
                o1["attn_chunk"] = max(shape.seq_len, attn_chunk)
            d1cfg, d2cfg = _depth_cfg(cfg, 1), _depth_cfg(cfg, 2)
            d1, d2 = d1cfg.num_layers, d2cfg.num_layers
            c1 = _lower_cell(d1cfg, shape, mesh, qcfg, **o1)
            c2 = _lower_cell(d2cfg, shape, mesh, qcfg, **o1)
            t1 = hlo_stats.cost_terms(c1, c1.as_text(), chips, group)
            t2 = hlo_stats.cost_terms(c2, c2.as_text(), chips, group)
            per_layer = {k: (t2[k] - t1[k]) / (d2 - d1)
                         for k in ("flops", "bytes", "coll")}
            overhead = {k: t1[k] - d1 * per_layer[k]
                        for k in ("flops", "bytes", "coll")}
            total = {k: max(overhead[k] + eff_L * per_layer[k], whole[k])
                     for k in ("flops", "bytes", "coll")}
            result["per_layer"] = per_layer
            result["overhead"] = overhead
        except Exception as e:  # noqa: BLE001
            result["depth_diff_error"] = f"{type(e).__name__}: {e}"

    # the microbatch loop is itself a lax.scan (body counted once): scale
    # totals by M (slightly overcounts the once-per-step optimizer update)
    ub = microbatches if shape.kind == "train" else 1
    terms = hlo_stats.RooflineTerms(
        flops=total["flops"] * chips * ub,
        bytes_hbm=total["bytes"] * chips * ub,
        bytes_coll=total["coll"] * chips * ub, chips=chips)
    result["roofline"] = terms.as_dict()
    mf = hlo_stats.model_flops(cfg, shape, shape.kind)
    result["model_flops"] = mf
    result["useful_ratio"] = mf / max(terms.flops, 1.0)
    kb = hlo_stats.kernel_modeled_bytes(cfg, shape, shape.kind,
                                        qcfg.bits if qcfg else None)
    result["kernel_modeled"] = {
        "bytes": kb,
        "t_memory": kb / (chips * hlo_stats.HBM_BW),
        "t_step": max(kb / (chips * hlo_stats.HBM_BW), terms.t_compute,
                      terms.t_collective),
    }

    if verbose:
        r = result["roofline"]
        print(f"{arch} {shape_name} {mesh_kind} [{result['quant']}]: "
              f"compute={r['t_compute']:.3e}s memory={r['t_memory']:.3e}s "
              f"collective={r['t_collective']:.3e}s -> {r['bottleneck']} "
              f"(compile {result['compile_secs']:.0f}s)")
        print("  memory_analysis:", result["memory"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single",
                    help="single | multi | 'd,m' (e.g. 2,4 for tests)")
    ap.add_argument("--quant", default="",
                    help="e.g. W2A16g128, W4A4, W4A16g128; empty = fp16")
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--no-block-correction", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--attn-seq-parallel", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--serve-sharding", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--kv-bits", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    res = run_cell(args.arch, args.shape, args.mesh, args.quant,
                   attn_chunk=args.attn_chunk,
                   block_correction=not args.no_block_correction,
                   microbatches=args.microbatches,
                   seq_parallel=args.seq_parallel,
                   attn_seq_parallel=args.attn_seq_parallel,
                   grad_compression=args.grad_compression,
                   serve_sharding=args.serve_sharding,
                   kv_bits=args.kv_bits or None)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1, default=str)
    return 0 if res["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
