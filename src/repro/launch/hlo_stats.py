"""Roofline accounting from compiled XLA artifacts.

Three terms per (arch x shape x mesh), in seconds (v5e constants):

    compute    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
    memory     = HLO_bytes / (chips * 819e9 B/s HBM)
    collective = collective_bytes / (chips * 50e9 B/s per ICI link)

``cost_analysis`` counts a ``lax.scan`` body ONCE (verified empirically), so
programs that scan over layers undercount by ~L.  The dry-run therefore
lowers a SINGLE block separately (with inner chunk-scans widened to one trip)
and composes:   total = whole_program + (L-1) * per_block.   Documented
approximation; the MODEL_FLOPS/HLO_FLOPs ratio in the table is the sanity
check on it.

Collective bytes are parsed from optimized HLO text with ring-model factors:
all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all (n-1)/n,
collective-permute 1.0 (n = participant group size from replica_groups).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

# TPU v5e per-chip constants (assignment-specified)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_FACTORS = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: float(n - 1),   # applied to the (small) result
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


def collective_bytes(hlo_text: str, default_group: int) -> Dict:
    """Sum modeled bytes-on-wire per collective kind."""
    per_kind: Dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.groups()
        if "-done" in line.split("=")[1][:40]:
            continue
        size = DTYPE_BYTES.get(dtype, 4)
        if dims:
            size *= int(np.prod([int(d) for d in dims.split(",")]))
        gm = _GROUPS_RE.search(line)
        if gm:
            n = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            n = int(gi.group(2)) if gi else default_group
        n = max(n, 2)
        per_kind.setdefault(kind, 0.0)
        per_kind[kind] += size * _FACTORS[kind](n)
        count += 1
    return {"per_kind": per_kind, "total": sum(per_kind.values()),
            "n_ops": count}


_HOST_XFER_RE = re.compile(
    r"=\s+[\w\[\],\.\s()]*?(copy-start|copy)\([^\n]*is_host_transfer=true")
_INFEED_RE = re.compile(r"=\s+[\w\[\],\.\s()]*?\b(infeed|outfeed)\(")


def host_transfer_ops(hlo_text: str) -> int:
    """Count ops in optimized HLO that move data across the host boundary
    (``is_host_transfer=true`` copies plus infeed/outfeed).  The HLO lint
    pins this to ZERO for the hot serving/recon programs: a nonzero count
    means a host value leaked into the jitted computation."""
    n = 0
    for line in hlo_text.splitlines():
        if _HOST_XFER_RE.search(line) or _INFEED_RE.search(line):
            n += 1
    return n


def collective_op_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective ops per kind in optimized HLO (same matcher as
    ``collective_bytes``, without the byte model) — the HLO lint asserts
    the observed kinds are a subset of the program's contract (e.g. the
    sharded recon step performs exactly one fused all-gather)."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done" in line.split("=")[1][:40]:
            continue
        kind = m.group(3)
        counts[kind] = counts.get(kind, 0) + 1
    return counts


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    bytes_hbm: float
    bytes_coll: float
    chips: int

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.bytes_coll / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_total(self) -> float:
        # roofline: overlapped execution -> max term bounds the step
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> Dict:
        return {
            "flops": self.flops, "bytes_hbm": self.bytes_hbm,
            "bytes_coll": self.bytes_coll, "chips": self.chips,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck, "t_total": self.t_total,
        }


def cost_terms(compiled, hlo_text: str, chips: int, default_group: int,
               scale: float = 1.0) -> Dict:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0)) * scale
    bts = float(ca.get("bytes accessed", 0.0)) * scale
    coll = collective_bytes(hlo_text, default_group)
    return {"flops": flops, "bytes": bts,
            "coll": coll["total"] * scale, "coll_detail": coll}


def compose(whole: Dict, block: Optional[Dict], n_layers: int,
            chips: int) -> RooflineTerms:
    """total = whole + (L-1) * block   (scan-body single-count correction)."""
    f, b, c = whole["flops"], whole["bytes"], whole["coll"]
    if block is not None and n_layers > 1:
        f += (n_layers - 1) * block["flops"]
        b += (n_layers - 1) * block["bytes"]
        c += (n_layers - 1) * block["coll"]
    # per-chip: cost_analysis on SPMD-partitioned modules is per-device
    return RooflineTerms(flops=f * chips, bytes_hbm=b * chips,
                         bytes_coll=c * chips, chips=chips)


def kernel_modeled_bytes(cfg, shape, kind: str, bits: Optional[int]) -> float:
    """Analytic lower bound on HBM traffic per step with fully-fused kernels
    (the Pallas path: packed weights DMA'd once, dequant in VMEM, flash
    attention never materializing scores).  Used as the optimized-kernel
    roofline line next to the measured XLA upper bound — the CPU backend
    neither fuses bf16 chains nor models VMEM residency (§Perf)."""
    n_active = cfg.active_param_count()
    wbytes = n_active * (CONTAINER := {2: 0.25, 3: 0.5, 4: 0.5, 8: 1.0}.get(
        bits, 2.0))
    hd = cfg.resolved_head_dim
    B, S = shape.global_batch, shape.seq_len
    kv_per_tok = 2 * cfg.num_kv_heads * hd * 2 * cfg.num_layers
    if cfg.family in ("rwkv", "hybrid"):
        kv_per_tok = 0   # O(1) state
    act_bytes = 0.0
    if kind == "train":
        # params fwd+bwd (3x streams) + opt state + remat carries
        return 3 * n_active * 2 + n_active * 8 + B * S * cfg.d_model * 2 * \
            cfg.num_layers
    if kind == "prefill":
        return wbytes + B * S * kv_per_tok + B * S * cfg.d_model * 2 * \
            cfg.num_layers * 4
    # decode: read weights once + read full KV cache + write one slot
    state = (cfg.num_layers * B * cfg.num_heads * hd * hd * 4
             if cfg.family in ("rwkv", "hybrid") else B * S * kv_per_tok)
    return wbytes + state + act_bytes


def model_flops(cfg, shape, kind: str) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D per forward token (decode/
    prefill), N = active params."""
    n = cfg.active_param_count()
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch                      # decode: one token each
    return 2.0 * n * tokens
