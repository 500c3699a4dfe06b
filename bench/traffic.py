"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws an open-loop request schedule.

The schedule is the mix's own: the prompt and output lengths are the
distribution's quantiles at ``(i + 0.5) / n`` and the inter-arrival gaps
the exponential's, each list put in an order drawn from the mix's
``schedule_seed``.  The run's seed draws the token ids.  So every seed
offers the same work at the same times; what a seed changes is the text
(and, elsewhere, the weights).  Shuffling the order by the run's seed as
well made the 90th-percentile time to first token of one cell spread by
87% of its median over six seeds: at four fifths of capacity the tail is
set by which long requests happen to arrive together.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float               # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped length distribution."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    """Requests due in a window of ``seconds``: the offered rate times the
    window, so every seed offers the same load."""
    return max(1, int(round(mix["rate_rps"] * seconds)))


def draw(mix: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = count(mix, seconds)
    order = np.random.default_rng(mix["schedule_seed"])
    prompts = order.permutation(_quantiles(mix["prompt_tokens"], n))
    outputs = order.permutation(_quantiles(mix["output_tokens"], n))
    q = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-q) / mix["rate_rps"])
    rng = np.random.default_rng(seed)
    # scale the gaps so all n arrivals fall inside the window
    due = np.cumsum(gaps) * seconds / (gaps.sum() + gaps.mean())
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        reqs.append(Planned(i, float(due[i]), ids, int(outputs[i])))
    return reqs


def longest(mix: dict) -> int:
    """Largest prompt plus output the mix can ask for."""
    return mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]


def prompt_buckets(lo: int, hi: int, min_bucket: int = 8) -> List[int]:
    """Power-of-two prefill buckets that prompts of ``lo..hi`` tokens land
    in (the serving engine's bucket rule)."""
    out, b = [], min_bucket
    while b < lo:
        b *= 2
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def warm_lengths(mix: dict) -> List[int]:
    """One prompt length per bucket the mix's prompts land in."""
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    return [min(max(b, lo), hi) for b in prompt_buckets(lo, hi)]
