import os
# reprolint: ok[env-read] — intentional WRITE that must run before jax's first import locks the device count
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Run the full dry-run matrix: every (arch x shape) cell on the single-pod
16x16 mesh AND the 2x16x16 multi-pod mesh, plus the paper-representative
quantized-serving variants (W2A16g128 decode / W4A4 prefill).

    PYTHONPATH=src python -m benchmarks.dryrun_matrix [--archs a,b] [--quick]

Writes one JSON per cell to artifacts/dryrun/.
"""

import argparse
import gc
import json
import sys
import time
import traceback

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def cell_name(arch, shape, mesh, quant, opts=""):
    q = quant or "fp16"
    o = f"_{opts}" if opts else ""
    return f"{arch}__{shape}__{mesh}__{q}{o}.json"


def run_one(arch, shape, mesh, quant="", **kw):
    from repro.launch.dryrun import run_cell
    path = os.path.join(ART, cell_name(arch, shape, mesh, quant,
                                       kw.pop("tag", "")))
    if os.path.exists(path) and not kw.pop("force", False):
        print(f"[skip-cached] {path}")
        return json.load(open(path))
    kw.pop("tag", None)
    t0 = time.time()
    try:
        res = run_cell(arch, shape, mesh, quant, verbose=False, **kw)
    except Exception as e:  # noqa: BLE001
        res = {"arch": arch, "shape": shape, "mesh": mesh,
               "quant": quant or "fp16", "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    res["wall_secs"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(res, f, indent=1, default=str)
    r = res.get("roofline", {})
    print(f"[{res['status']:7s}] {arch} {shape} {mesh} "
          f"{quant or 'fp16'} ({res['wall_secs']:.0f}s) "
          + (f"bottleneck={r.get('bottleneck')}" if r else
             res.get("why", res.get("error", ""))[:90]))
    gc.collect()
    return res


def run_tp_serve_cell(tp: int = 4, n_devices: int = 8):
    """TP serving sanitizer cell: uniform requests through the shard-mapped
    ``serve_scheduled`` loop end to end under
    ``sanitized(transfer_guard=True)`` — same exemption rules as the
    recon mesh path (explicit ``device_put`` placements are allowed, any
    implicit dispatch-time reshard of params/cache trips the guard).

    The roofline cells above only LOWER the TP decode step (AOT); this cell
    actually runs it on a ``serve_mesh(tp=...)`` submesh of the fake-device
    host so the matrix also proves the serving path executes guard-clean.
    """
    import numpy as np
    from benchmarks.common import SANITIZER, calib_batches, run_sanitized
    from repro.configs import get_reduced_config
    from repro.core import pack_model, quantize_model
    from repro.launch.mesh import serve_mesh
    from repro.launch.scheduler import Request, serve_scheduled
    from repro.launch.serve import parse_quant
    from repro.models import get_model

    path = os.path.join(ART, f"tp_serve_sanitize__tp{tp}.json")
    cfg = get_reduced_config("llama2-7b").replace(dtype="float32")
    model = get_model(cfg)
    import jax
    params = model.init_params(jax.random.PRNGKey(0))
    qcfg = parse_quant("W4A16g16")
    pq, qmeta, _ = quantize_model(cfg, params, calib_batches(cfg), qcfg,
                                  method="none", init="rtn")
    packed = pack_model(cfg, pq, qmeta, qcfg)
    mesh = serve_mesh(tp=tp, n_devices=n_devices)
    prompts = np.random.RandomState(0).randint(
        1, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    reqs = [Request(rid=b, prompt=p, max_new_tokens=4)
            for b, p in enumerate(prompts)]
    kw = dict(slots=len(reqs), mesh=mesh, tp_shard=True)
    t0 = time.time()
    # warm compile outside the guard (compilation device_puts constants);
    # the guarded run below must then dispatch with zero implicit transfers
    serve_scheduled(cfg, packed, reqs, **kw)
    run_sanitized(lambda: serve_scheduled(cfg, packed, reqs, **kw))
    res = {"cell": "tp_serve_sanitize", "tp": tp, "mesh": str(mesh),
           "quant": "W4A16g16",
           "status": "ok" if SANITIZER["clean"] else "error",
           "sanitizer_clean": SANITIZER["clean"],
           "why": SANITIZER["why"], "wall_secs": time.time() - t0}
    with open(path, "w") as f:
        json.dump(res, f, indent=1, default=str)
    print(f"[{res['status']:7s}] tp_serve_sanitize tp={tp} "
          f"sanitizer_clean={res['sanitizer_clean']} "
          f"({res['wall_secs']:.0f}s) {res['why'][:90]}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="")
    ap.add_argument("--quick", action="store_true",
                    help="single mesh only, no quantized variants")
    ap.add_argument("--tp-serve-only", action="store_true",
                    help="run only the TP serving sanitizer cell")
    args = ap.parse_args(argv)
    os.makedirs(ART, exist_ok=True)

    if args.tp_serve_only:
        return 0 if run_tp_serve_cell()["sanitizer_clean"] else 1

    from repro.configs import ARCH_IDS, SHAPES
    archs = (args.archs.split(",") if args.archs
             else [a for a in ARCH_IDS])

    for arch in archs:
        for shape in SHAPES:
            run_one(arch, shape.name, "single")
            if args.quick:
                continue
            # multi-pod: compile/memory proof only (the roofline table is
            # single-pod per the assignment; depth-diff costs 2 extra
            # compiles per cell)
            run_one(arch, shape.name, "multi", block_correction=False)
            # paper-representative quantized serving variants
            if shape.kind == "decode":
                run_one(arch, shape.name, "single", "W2A16g128")
            if shape.kind == "prefill":
                run_one(arch, shape.name, "single", "W4A4")
    if not args.quick:
        run_tp_serve_cell()
    return 0


if __name__ == "__main__":
    sys.exit(main())
