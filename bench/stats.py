"""Latency and rate arithmetic over the window's wall-clock stamps.

A request's record holds its due time, the time the harness handed it to
the engine, the start of the engine tick that admitted it, and the host
time at which each of its tokens came back.  All times are seconds after
the window opened.
"""
from __future__ import annotations

from typing import Dict, List

from .common import percentile


def ttft_s(rec: dict, end_s: float) -> float:
    """Due time to first token; a request that never produced one is
    counted as waiting until the run ended."""
    first = rec["times"][0] if rec["times"] else end_s
    return first - rec["due"]


def tpot_s(rec: dict):
    """(last token - first token) / (tokens - 1), or None below 2 tokens."""
    t = rec["times"]
    if len(t) < 2:
        return None
    return (t[-1] - t[0]) / (len(t) - 1)


def end_to_end(reqs: Dict[int, dict], seconds: float,
               end_s: float) -> Dict[str, float]:
    """The window's end-to-end serving metrics, over every request due in
    it.  ``tokens_per_s`` counts the tokens that reached the host inside
    the window.  Time to first token has its median and 75th percentile:
    its 90th percentile over the 70 requests of a 50 s window spread by 20%
    of its median from run to run on one chip (the occasional long engine
    tick lands on the tail), more than a bound can hold."""
    ttft = [ttft_s(r, end_s) for r in reqs.values()]
    tpot = [x for x in (tpot_s(r) for r in reqs.values()) if x is not None]
    inside = sum(1 for r in reqs.values() for t in r["times"] if t <= seconds)
    return {"ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p75_ms": 1e3 * percentile(ttft, 75),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tpot_p90_ms": 1e3 * percentile(tpot, 90),
            "tokens_per_s": inside / seconds}


def queue_waits_ms(reqs: Dict[int, dict]) -> List[float]:
    """Due time to the start of the tick that admitted the request."""
    return [1e3 * (r["admit"] - r["due"]) for r in reqs.values()
            if r["admit"] is not None]


def lag_ms(reqs: Dict[int, dict]) -> List[float]:
    """How late the generator handed each request to the engine."""
    return [1e3 * (r["added"] - r["due"]) for r in reqs.values()
            if r["added"] is not None]
