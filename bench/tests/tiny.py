"""A cell small enough for the CPU: the serving configuration's structure
(GQA, SwiGLU, W2 g128 packed weights with AWQ scales) at toy widths."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "mistral7b-w2-decode"


def spec(limit=None) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=2,
               num_key_value_heads=1, num_hidden_layers=2, vocab_size=512,
               head_dim=128)
    if limit is not None:
        cfg["check"] = {"max_logit_gap": limit}
    mix = dict(mix, slots=4, wave_requests=6,
               prompt_lens=[8, 16], budget_range=[4, 12], mean_gap_steps=1,
               check_requests=3)
    return {"bench": bench, "cell": cell, "config": cfg, "mix": mix}
