"""A cell of the latent-attention expert model small enough for the CPU:
Moonlight's structure (MLA with a shared rope key, a dense first layer,
sigmoid-routed experts with shared experts, W2 g128 packed weights with AWQ
scales) at toy widths."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "moonlight-w2-decode"
TOY = dict(hidden_size=256, intermediate_size=256, moe_intermediate_size=128,
           num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=3,
           vocab_size=512, kv_lora_rank=128, qk_nope_head_dim=64,
           qk_rope_head_dim=64, v_head_dim=64, n_routed_experts=8,
           num_experts_per_tok=2)


def spec(limit=None) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = copy.deepcopy(json.loads((ROOT / conf["file"]).read_text()))
    mix = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    cfg.update(TOY)
    if limit is not None:
        cfg["check"] = dict(cfg["check"], max_logit_gap=limit)
    mix = dict(mix, slots=4, wave_requests=6, prompt_lens=[8, 16],
               budget_range=[4, 12], mean_gap_steps=1, check_requests=3)
    return {"bench": bench, "cell": cell, "config": cfg, "mix": mix}
