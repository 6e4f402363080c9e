"""One-command end-to-end quality harness (ZeroQuant-V2's point: PTQ systems
must be judged by comprehensive end-to-end evaluation, not recon MSE).

    PYTHONPATH=src python -m repro.eval.harness --smoke

Runs, for FP and each PTQ method (RTN / AWQ / TesseraQ) at one quant config:

  * perplexity on held-out synthetic eval batches (fake-quant params);
  * synthetic multiple-choice accuracy (PIQA/ARC-style protocol);
  * the PACKED deployment artifact's perplexity under the XLA backend;
  * a **logits-parity gate** between the xla and pallas serve paths on the
    packed model — prefill plus >= 3 continuous-batched decode steps must
    agree to bf16 tolerance, otherwise the harness exits non-zero.

Results land in a machine-readable JSON (``--json``, default ``EVAL.json``)
so CI can archive a quality trajectory.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.core import pack_model, quantize_model
from repro.core.tesseraq import TesseraQConfig
from repro.data.pipeline import (DataConfig, SyntheticCorpus,
                                 calibration_batches, eval_batches)
from repro.eval.ppl import choice_accuracy, make_choice_tasks, perplexity
from repro.launch.scheduler import Request, serve_scheduled
from repro.launch.serve import parse_quant
from repro.models import get_model

# method rows: (label, quantize_model method, init)
METHODS = (("rtn", "none", "rtn"),
           ("awq", "none", "awq"),
           ("tesseraq", "tesseraq", "awq"))


def parity_gate(a: np.ndarray, b: np.ndarray, *, atol: float,
                rtol: float) -> dict:
    """THE cross-backend logits comparison — symmetric rtol reference
    (max of both magnitudes).  Every parity gate (this harness, the tests,
    ``chip_smoke.py``) must call this one helper so the gates cannot drift
    apart semantically or in tolerance."""
    diff = np.abs(a - b)
    scale = np.maximum(np.abs(a), np.abs(b))
    ok = bool(np.all(diff <= atol + rtol * scale))
    return {"ok": ok, "max_abs_diff": float(diff.max()),
            "steps_compared": int(a.shape[1]), "atol": atol, "rtol": rtol}


def logits_parity(cfg, packed, prompts, *, gen: int, atol: float,
                  rtol: float) -> dict:
    """Each row of ``prompts`` served as a request of ``gen`` tokens, all
    at once over one slot each: prefill + (gen-1) decode steps under both
    backends; allclose gate over every step's logits."""
    reqs = [Request(rid=b, prompt=p, max_new_tokens=gen)
            for b, p in enumerate(prompts)]
    runs = {b: serve_scheduled(cfg, packed, reqs, slots=len(reqs),
                               kernel_backend=b, collect_logits=True)
            for b in ("xla", "pallas")}
    return parity_gate(runs["xla"].logits_matrix(),
                       runs["pallas"].logits_matrix(),
                       atol=atol, rtol=rtol)


def run_harness(args) -> dict:
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    qcfg = parse_quant(args.quant)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.batch, seed=args.seed)

    calib = calibration_batches(data_cfg, 2, max(2, args.calib_samples // 2))
    calib = [{"tokens": jnp.asarray(b["tokens"][:, :-1])} for b in calib]
    evalb = eval_batches(data_cfg, args.eval_batches, args.batch)
    corpus = SyntheticCorpus(data_cfg)
    tasks = make_choice_tasks(corpus, args.tasks, args.seq_len)
    prompts = corpus.batch(0)["tokens"][:, :args.seq_len]
    tcfg = TesseraQConfig(par_iterations=args.par_iters,
                          steps_per_iteration=args.par_steps)

    out = {"arch": cfg.name, "qcfg": qcfg.tag, "rows": {}, "parity": {}}
    t0 = time.time()
    out["rows"]["fp"] = {
        "ppl": perplexity(cfg, params, evalb),
        "choice_acc": choice_accuracy(cfg, params, tasks),
        "secs": time.time() - t0,
    }
    print(f"[eval] fp: ppl={out['rows']['fp']['ppl']:.3f} "
          f"acc={out['rows']['fp']['choice_acc']:.3f}")

    parity_ok = True
    for label, method, init in METHODS:
        t0 = time.time()
        pq, qmeta, _ = quantize_model(cfg, params, calib, qcfg,
                                      method=method, init=init, tcfg=tcfg)
        packed = pack_model(cfg, pq, qmeta, qcfg)
        row = {
            "ppl": perplexity(cfg, pq, evalb),
            "choice_acc": choice_accuracy(cfg, pq, tasks),
            "ppl_packed_xla": perplexity(cfg, packed, evalb, backend="xla"),
        }
        row["secs"] = time.time() - t0
        out["rows"][label] = row
        print(f"[eval] {label}: ppl={row['ppl']:.3f} "
              f"acc={row['choice_acc']:.3f} "
              f"packed_xla_ppl={row['ppl_packed_xla']:.3f}")
        if label == args.parity_method:
            gate = logits_parity(cfg, packed, prompts,
                                 gen=args.parity_steps + 1,
                                 atol=args.parity_atol, rtol=args.parity_rtol)
            out["parity"][label] = gate
            parity_ok = parity_ok and gate["ok"]
            print(f"[eval] parity {label} (xla vs pallas, prefill + "
                  f"{gate['steps_compared'] - 1} decode steps): "
                  f"{'PASS' if gate['ok'] else 'FAIL'} "
                  f"(max |d|={gate['max_abs_diff']:.2e})")
    out["parity_ok"] = parity_ok
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="W4A16g32")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--eval-batches", type=int, default=2)
    ap.add_argument("--tasks", type=int, default=8)
    ap.add_argument("--calib-samples", type=int, default=8)
    ap.add_argument("--par-iters", type=int, default=2)
    ap.add_argument("--par-steps", type=int, default=8)
    ap.add_argument("--parity-method", default="tesseraq",
                    help="which method's packed model the backend-parity "
                         "gate runs on")
    ap.add_argument("--parity-steps", type=int, default=3,
                    help="decode steps compared (on top of prefill)")
    ap.add_argument("--parity-atol", type=float, default=5e-2)
    ap.add_argument("--parity-rtol", type=float, default=2e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="EVAL.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (reduced arch, short calib)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.reduced = True
        args.seq_len, args.batch = 16, 2
        args.eval_batches, args.tasks = 1, 4
        args.par_iters, args.par_steps = 1, 2

    out = run_harness(args)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return 0 if out["parity_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
