"""Serving driver: waves of requests through ``serve_scheduled`` on the packed
model, checked against the plain reference.

The window drives back-to-back calls of
``repro.launch.scheduler.serve_scheduled`` ("waves"), each with one wave of
the mix's requests, until ``seconds`` have passed: only whole waves run, so
every request of the window is known to the reduction.  Every argument the
mix does not set stays at the program's default.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, traffic
from bench import weights as W
from bench.reference import dense_lm


def model_sizes(cfg: dict) -> dict:
    """The configuration's model sizes under the names this harness uses
    (those of the model's ``config.json``)."""
    return {k: cfg[k] for k in ("hidden_size", "intermediate_size",
                                "num_attention_heads", "num_key_value_heads",
                                "num_hidden_layers", "vocab_size", "head_dim",
                                "rms_norm_eps", "rope_theta")}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


@dataclass
class Window:
    wall_s: float
    waves: List = field(default_factory=list)     # [(plan, ServeResult)]

    @property
    def attempted(self) -> int:
        return sum(len(plan) for plan, _ in self.waves)

    def requests(self):
        """(wave, planned request, served tokens) of every request."""
        for k, (plan, res) in enumerate(self.waves):
            for p in plan:
                yield k, p, np.asarray(res.requests[p.rid]["tokens"])


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, *, chips: int = 1):
        if chips != 1:
            raise ValueError("the serving driver runs on one chip")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = model_sizes(cfg)
        self.q = cfg["quant"]
        self.pcfg = program_config(cfg)
        self.width = traffic.width(mix)
        # the program attends over the whole causal past; that equals the
        # model's sliding window only while no sequence outgrows the window
        window = cfg.get("sliding_window")
        if window is not None and self.width > window:
            raise ValueError(f"mix width {self.width} exceeds the "
                             f"configuration's sliding_window {window}")
        self.params = self.steps = None

    # -- the program ---------------------------------------------------------
    def _serve(self, plan):
        from repro.launch.scheduler import Request, serve_scheduled
        reqs = [Request(rid=p.rid, prompt=p.prompt,
                        max_new_tokens=p.max_new_tokens, arrival=p.arrival)
                for p in plan]
        return serve_scheduled(self.pcfg, self.params, reqs,
                               slots=self.mix["slots"],
                               max_seq=self.width,
                               kernel_backend=self.q["kernel_backend"],
                               compiled=self.steps)

    def setup(self):
        """Weights from the seed, then one short wave with a request of each
        prompt length, which compiles (or loads) every program the window
        runs: the prefill at each length, slot install, decode step."""
        from repro.launch.scheduler import compile_sched_steps
        self.params = jax.block_until_ready(W.make_packed_params(
            self.seed, self.m, self.q, self.cfg["weights"]))
        self.steps = compile_sched_steps(
            self.pcfg, max_seq=self.width,
            kernel_backend=self.q["kernel_backend"])
        rng = np.random.default_rng(int(self.seed))
        warm = [traffic.Planned(
            rid=i, prompt=rng.integers(0, self.m["vocab_size"], n,
                                       dtype=np.int32),
            max_new_tokens=2, arrival=0)
            for i, n in enumerate(self.mix["prompt_lens"])]
        self._serve(warm)

    def window(self, seconds: float) -> Window:
        t0 = time.time()
        out = Window(wall_s=0.0)
        while True:
            plan = traffic.wave(self.mix, self.m["vocab_size"], self.seed,
                                len(out.waves))
            with jax.profiler.TraceAnnotation("bench.wave"):
                out.waves.append((plan, self._serve(plan)))
            if time.time() - t0 >= seconds:
                break
        out.wall_s = time.time() - t0
        return out

    def release(self):
        self.params = None

    # -- metrics ---------------------------------------------------------------
    @staticmethod
    def end_to_end(win: Window) -> dict:
        useful = sum(res.useful_tokens for _, res in win.waves)
        steps = sum(res.steps for _, res in win.waves)
        return {"out_tok_s": useful / win.wall_s,
                "tpot_ms": 1e3 * win.wall_s / steps}

    def layer_context(self, win: Window, tr, peaks: dict) -> dict:
        """What the per-layer readers read (``bench/metrics/*.py``)."""
        return {"window": win, "trace": tr, "peaks": peaks, "m": self.m,
                "q": self.q, "mix": self.mix, "costs": costs}

    # -- correctness -----------------------------------------------------------
    def sample(self, win: Window):
        """The requests the reference checks: the longest of the window
        (prompt plus output), then others drawn from the seed."""
        reqs = list(win.requests())
        n = min(self.mix["check_requests"], len(reqs))
        longest = max(range(len(reqs)), key=lambda i: (
            len(reqs[i][1].prompt) + reqs[i][1].max_new_tokens, -i))
        rest = [i for i in range(len(reqs)) if i != longest]
        rng = np.random.default_rng([int(self.seed), 7])
        pick = [longest] + list(rng.choice(rest, n - 1, replace=False))
        return [reqs[i] for i in pick]

    def _reference_inputs(self, picked):
        """Prompt plus served tokens (all but the last) of each picked
        request, padded at the end to the cache width."""
        S = self.width
        toks = np.zeros((len(picked), S), np.int32)
        where = []                          # (row, position, served token)
        for r, (_, p, served) in enumerate(picked):
            seq = np.concatenate([p.prompt, served[:-1]])
            toks[r, :len(seq)] = seq
            for j, t in enumerate(served):
                where.append((r, len(p.prompt) - 1 + j, int(t)))
        return toks, np.asarray(where, np.int32)

    def logit_gaps(self, picked, *, precision: str = "float32",
                   chosen: str = "served") -> np.ndarray:
        """Per served position: how far below the float32 reference's best
        logit lies the token chosen there.  ``chosen="served"`` takes the
        program's token; ``chosen="reference"`` takes the token that the
        reference at ``precision`` puts first (the control)."""
        toks, where = self._reference_inputs(picked)
        ref = dense_lm.logits(self.seed, self.cfg, toks)
        rows, pos, served = (jnp.asarray(where[:, i]) for i in range(3))
        at = ref[rows, pos]                             # (n, V)
        if chosen == "served":
            tok = served
        else:
            low = dense_lm.logits(self.seed, self.cfg, toks,
                                  precision=precision)
            tok = jnp.argmax(low[rows, pos], -1)
            del low
        gap = at.max(-1) - jnp.take_along_axis(at, tok[:, None], -1)[:, 0]
        return np.asarray(gap)

    def check(self, win: Window):
        """(correct, {name: {value, limit}}, failed requests)."""
        failed = sum(int(len(served) != p.max_new_tokens)
                     for _, p, served in win.requests())
        picked = self.sample(win)
        gaps = self.logit_gaps(picked)
        limit = self.cfg["check"]["max_logit_gap"]
        checks = {
            "failed_requests": {"value": failed, "limit": 0},
            "max_logit_gap": {"value": float(gaps.max()), "limit": limit},
        }
        correct = failed == 0 and float(gaps.max()) <= limit
        return correct, checks, failed
