"""Shared benchmark substrate: one trained tiny LM reused by every table
(trained once, cached under artifacts/), plus calibration/eval sets.

CPU container note: paper-scale LLaMA checkpoints don't exist offline, so
every table reproduces the paper's *method orderings and deltas* on a small
model trained in-repo (DESIGN.md §7), at reduced PAR iteration counts.
"""
from __future__ import annotations

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced_config
from repro.core.tesseraq import TesseraQConfig
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.debug.sanitize import sanitized
from repro.eval.ppl import choice_accuracy, make_choice_tasks, perplexity
from repro.launch.steps import make_train_harness

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")
SEQ = 64
BATCH = 8


def train_steps() -> int:
    """Env-tunable training step count, read at CALL time rather than
    import time so CI and test runners can set BENCH_TRAIN_STEPS after
    this module has already been imported."""
    return int(os.environ.get("BENCH_TRAIN_STEPS", "150"))


def bench_tcfg() -> TesseraQConfig:
    """Reduced-but-real TesseraQ settings for CPU benches (env-tunable;
    read at call time, same rationale as ``train_steps``)."""
    return TesseraQConfig(
        par_iterations=int(os.environ.get("BENCH_PAR_K", "5")),
        steps_per_iteration=int(os.environ.get("BENCH_PAR_T", "25")),
        batch_size=4)


def bench_config():
    return get_reduced_config("llama2-7b").replace(
        num_layers=4, d_model=96, num_heads=4, num_kv_heads=4, d_ff=256,
        vocab_size=512, dtype="float32")


def data_config(cfg):
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                      global_batch=BATCH, seed=5)


def trained_model(cfg=None, tag="bench_lm"):
    """Train (or load cached) the benchmark LM."""
    cfg = cfg or bench_config()
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, f"{tag}.pkl")
    harness = make_train_harness(cfg, None, lr=2e-3)
    if os.path.exists(path):
        with open(path, "rb") as f:
            leaves = pickle.load(f)
        ref = harness.init_params(jax.random.PRNGKey(0))
        treedef = jax.tree_util.tree_structure(ref)
        params = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(a) for a in leaves])
        return cfg, params
    data = SyntheticCorpus(data_config(cfg))
    params = harness.init_params(jax.random.PRNGKey(0))
    opt = harness.init_opt(params)
    step_fn = jax.jit(harness.step_fn)   # reprolint: ok[jit-cache] — trains once per cached artifact
    for s in range(train_steps()):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        params, opt, m = step_fn(params, opt, batch)
    with open(path, "wb") as f:
        pickle.dump([np.asarray(a) for a in
                     jax.tree_util.tree_leaves(params)], f)
    return cfg, params


def calib_batches(cfg, n=2, bs=4):
    data = SyntheticCorpus(data_config(cfg))
    return [{"tokens": jnp.asarray(data.batch(10_000 + i)["tokens"][:bs, :-1])}
            for i in range(n)]


def eval_ppl_batches(cfg, n=4):
    data = SyntheticCorpus(data_config(cfg))
    return [{"tokens": data.batch(20_000 + i)["tokens"]} for i in range(n)]


def eval_tasks(cfg, n=40):
    data = SyntheticCorpus(data_config(cfg))
    return make_choice_tasks(data, n, SEQ)


def evaluate(cfg, params, tasks=None):
    out = {"ppl": perplexity(cfg, params, eval_ppl_batches(cfg))}
    if tasks is not None:
        out["acc"] = choice_accuracy(cfg, params, tasks)
    return out


def emit(table: str, name: str, metric: str, value, t_us: float = 0.0):
    print(f"{table},{name},{metric},{value},{t_us:.1f}")


SANITIZER = {"clean": True, "why": ""}


def run_sanitized(fn):
    """Run one TIMED bench section under ``sanitized(transfer_guard=True)``
    (leak checking off — its bookkeeping would distort the timings).

    A guard trip is recorded once (failing the bench's ``sanitizer_clean``
    gate) and the section re-runs unguarded, so the artifact still gets
    written with the regression on record instead of dying mid-run; real
    (non-guard) failures re-raise from the unguarded rerun."""
    try:
        with sanitized(transfer_guard=True, check_leaks=False):
            return fn()
    except Exception as e:                     # noqa: BLE001 — see docstring
        if SANITIZER["clean"]:
            SANITIZER["clean"] = False
            SANITIZER["why"] = f"{type(e).__name__}: {e}"
        return fn()


def sanitizer_gate(out: dict) -> bool:
    """The ``sanitizer_clean`` gate every bench artifact must carry: all
    timed sections ran without tripping the transfer guard."""
    ok = SANITIZER["clean"]
    if not ok:
        out["sanitizer_why"] = SANITIZER["why"]
    return gate(out, "sanitizer_clean", threshold=1.0, measured=float(ok),
                ok=ok, cmp=">=")


def gate(out: dict, name: str, *, threshold, measured, ok, cmp) -> bool:
    """One machine-readable gate record appended to ``out["gates"]`` — THE
    shared schema ({name, threshold, measured, ok, cmp}) of the bench
    artifacts (BENCH_recon.json); a bench run must fail if any gate is not
    ok."""
    out["gates"].append({"name": name, "threshold": float(threshold),
                         "measured": float(measured), "ok": bool(ok),
                         "cmp": cmp})
    print(f"gate: {name}: {'PASS' if ok else 'FAIL'} "
          f"(measured {measured:.4g}, want {cmp} {threshold:.4g})")
    return bool(ok)
