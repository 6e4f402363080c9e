"""Reduction of a profiler trace to device busy time, kernel time and idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain tuples:
the operations that ran on each TPU (the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane), each execution of a compiled program there (its
``XLA Modules`` line) and the host's events.  Everything after that is
interval arithmetic on those tuples, testable without a trace file.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    # per device plane: [(op name, start_ns, end_ns)] of its operations;
    # loops and conditionals, which contain other operations, are kept apart
    device_ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    containers: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # per device plane: [(program name, start_ns, end_ns)] of each execution
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # every host event: (name, start_ns, end_ns)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> Optional[Tuple[float, float]]:
        """(start, end) of the benchmark's window span, if it was traced."""
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        return (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else None

    def busy_ops(self, plane: str):
        """Every operation of a plane, containers included."""
        return self.device_ops[plane] + self.containers.get(plane, [])


def find_file(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def op_name(text: str) -> str:
    """``%quant_gemv_op.32 = bf16[...] custom-call(...)`` -> ``quant_gemv_op``:
    the HLO instruction's name without the numeric suffix XLA adds to each
    instance."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _is_container(text: str) -> bool:
    return " while(" in text or " conditional(" in text


def module_name(text: str) -> str:
    """``jit_prefill_step(1207...)`` -> ``jit_prefill_step``."""
    return text.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, cont, mods = [], [], []
            seen: Dict[str, Tuple[str, bool]] = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        text = e.name
                        if text not in seen:
                            seen[text] = (op_name(text), _is_container(text))
                        name, is_cont = seen[text]
                        (cont if is_cont else ops).append(
                            (name, e.start_ns, e.end_ns))
                elif line.name == "XLA Modules":
                    mods.extend((module_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events)
            tr.device_ops[plane.name] = ops
            tr.containers[plane.name] = cont
            tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.duration_ns > 0)
    return tr


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union([(s, e) for _, s, e in ops], lo, hi))


def gaps(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which no operation ran."""
    out, cur = [], lo
    for s, e in union([(s, e) for _, s, e in ops], lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def named(events, name: str, lo: float, hi: float):
    """Events starting inside [lo, hi] whose name is ``name``."""
    return [(n, s, e) for n, s, e in events if n == name and lo <= s < hi]


def top_ops(ops, lo: float, hi: float, n: int = 10):
    """[[name, seconds]] of the ``n`` operation names that took most device
    time."""
    tot: Dict[str, float] = {}
    for name, s, e in ops:
        if lo <= s < hi:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_activity(host, s: float, e: float) -> str:
    """What the host was doing in [s, e]: the host event that overlaps it
    most, the benchmark's own spans (``bench.*``) only where nothing else
    does."""
    best, best_ov, best_bench, bench_ov = None, 0.0, None, float("inf")
    for name, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov <= 0:
            continue
        if name.startswith("bench."):
            # innermost benchmark span that covers the gap
            if he - hs < bench_ov:
                best_bench, bench_ov = name, he - hs
        elif ov > best_ov:
            best, best_ov = name, ov
    return best or best_bench or "none"


def idle_gaps(tr: Trace, ops, lo: float, hi: float, n: int = 10):
    """[[host activity, seconds]] of the ``n`` longest idle gaps."""
    longest = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[host_activity(tr.host, s, e), (e - s) * 1e-9]
            for s, e in longest]
