"""Serving driver for a DeepSeek-V3-style model (latent attention, dropless
experts): waves of requests through ``serve_scheduled`` on the packed model,
checked against the plain reference ``bench/reference/mla_moe_lm.py``.

Everything but the model is ``bench/drivers/serve.py``'s: the waves, the
window, the end-to-end metrics and the sample of requests the reference
checks.  The comparison gives the reference the experts the program routed
each token to (``ServeResult`` records them) and checks those choices
against the reference's own scores (``max_route_deficit``) beside the
logits (``max_logit_gap``): with random weights, top-k routing in two
precisions diverges too far for a comparison of logits alone (PERF.md,
Findings).  The program's entry points for this family are imported when this
module loads, so a program without them fails at once, before any weights
are made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs_mla_moe, traffic
from bench import weights_mla_moe as W
from bench.drivers import serve
from bench.reference import mla_moe_lm
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.models.mla_moe import serve_params


def program_config(cfg: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for the configuration file."""
    return ModelConfig(
        name=cfg["name"], family="mla_moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["v_head_dim"],
        moe=MoEConfig(num_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      shared_experts=cfg["n_shared_experts"],
                      routed_scaling=float(cfg["routed_scaling_factor"]),
                      dense_layers=cfg["first_k_dense_replace"],
                      dense_d_ff=cfg["intermediate_size"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


def packed_params(seed: int, cfg: dict):
    """The packed tree from the seed, with the absorbed attention weights
    the serving path makes once at set-up."""
    params = W.make_packed_params(seed, W.sizes(cfg), cfg["quant"],
                                  cfg["weights"])
    return serve_params(params, program_config(cfg))


class Driver(serve.Driver):
    def __init__(self, cfg: dict, mix: dict, seed: int, *, chips: int = 1):
        if chips != 1:
            raise ValueError("the serving driver runs on one chip")
        if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
                cfg["q_lora_rank"]) != ("sigmoid", "noaux_tc", 1, None):
            raise ValueError("the program models sigmoid noaux_tc routing "
                             "in one group without a query low-rank "
                             "projection")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.m = W.sizes(cfg)
        self.q = cfg["quant"]
        self.pcfg = program_config(cfg)
        self.width = traffic.width(mix)
        if self.width > cfg["max_position_embeddings"]:
            raise ValueError(f"mix width {self.width} exceeds the model's "
                             f"{cfg['max_position_embeddings']} positions")
        self.params = self.steps = None

    def setup(self):
        """Weights from the seed, then one short wave with a request of each
        prompt length, which compiles (or loads) every program the window
        runs."""
        from repro.launch.scheduler import compile_sched_steps
        self.params = jax.block_until_ready(
            packed_params(self.seed, self.cfg))
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

    def layer_context(self, win, tr, peaks: dict) -> dict:
        return {"window": win, "trace": tr, "peaks": peaks, "m": self.m,
                "q": self.q, "mix": self.mix, "costs": costs_mla_moe}

    # -- correctness -------------------------------------------------------
    def sample(self, win):
        """``serve.Driver.sample``'s requests, each with the experts the
        program routed its tokens to (expert layers, positions, k)."""
        return [(k, p, served, win.waves[k][1].requests[p.rid]["experts"])
                for k, p, served in super().sample(win)]

    def _inputs(self, picked):
        """Prompt plus served tokens (all but the last) of each picked
        request, padded at the end to the cache width; where each served
        token was chosen; the given experts of every position, -1 on the
        padding."""
        toks, where = self._reference_inputs([x[:3] for x in picked])
        m = self.m
        given = np.full((len(picked), m["num_hidden_layers"]
                         - m["first_k_dense_replace"], self.width,
                         m["num_experts_per_tok"]), -1, np.int32)
        for r, (_, _, _, experts) in enumerate(picked):
            given[r, :, :experts.shape[1]] = experts
        return toks, where, given

    def compare(self, picked, *, precision: str = "float32",
                chosen: str = "served"):
        """(gaps, deficits) of the served positions of ``picked``: how far
        each chosen token's float32 reference logit lies below the
        reference's best, and the largest routing deficit (see
        ``bench/reference/mla_moe_lm.py``) of each request's positions.
        ``chosen="served"`` judges the program's tokens with the program's
        routing given to the reference; ``chosen="reference"`` judges the
        reference at ``precision`` (the control) the same way, with its own
        tokens and routing."""
        toks, where, given = self._inputs(picked)
        rows, pos, served = (jnp.asarray(where[:, i]) for i in range(3))
        if chosen == "served":
            tok = served
        else:
            low, given, _ = mla_moe_lm.logits(self.seed, self.cfg, toks,
                                              precision=precision)
            tok = jnp.argmax(low[rows, pos], -1)
            del low
        ref, _, deficit = mla_moe_lm.logits(self.seed, self.cfg, toks,
                                            experts=given)
        at = ref[rows, pos]
        gap = at.max(-1) - jnp.take_along_axis(at, tok[:, None], -1)[:, 0]
        return np.asarray(gap), np.asarray(deficit.max(axis=(1, 2)))

    def check(self, win):
        """(correct, {name: {value, limit}}, failed requests)."""
        failed = sum(int(len(served) != p.max_new_tokens)
                     for _, p, served in win.requests())
        gaps, deficits = self.compare(self.sample(win))
        lim = self.cfg["check"]
        checks = {
            "failed_requests": {"value": failed, "limit": 0},
            "max_logit_gap": {"value": float(gaps.max()),
                              "limit": lim["max_logit_gap"]},
            "max_route_deficit": {"value": float(deficits.max()),
                                  "limit": lim["max_route_deficit"]},
        }
        correct = failed == 0 and all(
            c["value"] <= c["limit"] for c in checks.values())
        return correct, checks, failed
