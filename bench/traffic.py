"""One generator for every serving traffic mix: a data file of parameters in,
waves of requests out.

A mix file (``bench/traffic/<name>.json``) fixes the multiset of what a wave
holds: ``wave_requests`` requests whose prompt lengths are spread evenly over
``prompt_lens``, whose output budgets are spread evenly over
``budget_range`` (inclusive), and whose arrival gaps are the quantiles of a
Poisson distribution of mean ``mean_gap_steps``; ``source`` names where
the lengths come from and is not read.  The three lists are
shuffled by the wave's index alone, and the seed draws only the prompts'
token ids: the order of sizes changes how many decode steps a wave takes,
so a seed that shuffled them would change the work.  Every seed sends the
same requests, in size and time, with other tokens.  Arrivals are on the
scheduler's clock (decode steps), as
``repro.launch.scheduler.make_workload`` has them.

The cache width a mix is served at is not a parameter: it is the most
positions one of its requests takes, rounded up to ``WIDTH_MULTIPLE``, so
that no lane the dense store reserves is one that no request can fill.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

WIDTH_MULTIPLE = 64


@dataclass(frozen=True)
class Planned:
    """One request as planned: the program's ``Request`` is built from it."""
    rid: int
    prompt: np.ndarray          # (plen,) int32
    max_new_tokens: int
    arrival: int


def _spread(values, n: int) -> List[int]:
    """``n`` items cycling evenly through ``values``."""
    return [int(values[i % len(values)]) for i in range(n)]


def _even(lo: int, hi: int, n: int) -> List[int]:
    """``n`` integers evenly spaced over [lo, hi]."""
    if n == 1:
        return [lo]
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]


def _poisson_quantiles(mean: float, n: int) -> List[int]:
    """The (i + 0.5) / n quantiles of Poisson(mean), i < n."""
    out, k, cdf = [], 0, math.exp(-mean)
    pmf = cdf
    for i in range(n):
        u = (i + 0.5) / n
        while cdf < u:
            k += 1
            pmf *= mean / k
            cdf += pmf
        out.append(k)
    return out


def wave(mix: dict, vocab: int, seed: int, index: int) -> List[Planned]:
    """Wave ``index`` of a run with ``seed``: a pure function of its
    arguments."""
    n = mix["wave_requests"]
    order = np.random.default_rng(int(index))
    plens = order.permutation(_spread(mix["prompt_lens"], n))
    budgets = order.permutation(_even(*mix["budget_range"], n))
    gaps = order.permutation(_poisson_quantiles(mix["mean_gap_steps"], n - 1))
    # one request pairs the longest prompt with the longest answer, so that
    # every wave writes the last lane of the width
    top = plens == plens.max()
    if budgets[top].max() < budgets.max():
        a, b = int(np.flatnonzero(top)[0]), int(np.argmax(budgets))
        budgets[a], budgets[b] = budgets[b], budgets[a]
    arrivals = np.concatenate([[0], np.cumsum(gaps)]).astype(int)
    rng = np.random.default_rng([int(seed), int(index)])
    out = []
    for rid in range(n):
        prompt = rng.integers(0, vocab, int(plens[rid]), dtype=np.int32)
        out.append(Planned(rid=rid, prompt=prompt,
                           max_new_tokens=int(budgets[rid]),
                           arrival=int(arrivals[rid])))
    return out


def max_positions(mix: dict) -> int:
    """The most cache positions one request of the mix can take."""
    return max(mix["prompt_lens"]) + mix["budget_range"][1]


def width(mix: dict) -> int:
    """The cache width (``max_seq``) the mix is served at."""
    return -(-max_positions(mix) // WIDTH_MULTIPLE) * WIDTH_MULTIPLE
