"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

``load(path)`` flattens the trace into plain event dicts: per device plane
its program (XLA module) executions and its op events, and the host's
``bench.*`` annotations.  ``reduce(events)`` then works on those dicts
alone, so it can be checked on events kept as JSON.

What it gives, over the traced slice (the host's ``bench.slice`` span):
  window_s    length of the slice;
  busy_s      union of the op intervals, averaged over the devices;
  modules     per program: device seconds, executions, and within it the
              seconds of each Pallas kernel and of collectives;
  top_ops     the device ops that took most time;
  idle_gaps   the longest gaps with no op on device 0, each labelled by
              the host annotation it fell in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all)")
# a TPU op event is named by its HLO instruction: "%name.N = shape op(...)"
INSTR = re.compile(r"%?([^\s=]+)\s*=\s*(.*)", re.S)
OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
# ops whose events span the ops they run (left out of the top ops)
CONTAINERS = ("while", "conditional", "call")
HOST_SPANS = ("bench.slice", "bench.add", "bench.step", "bench.idle")


def newest_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                kind = ("module" if "Module" in line.name else
                        "op" if "Ops" in line.name else None)
                if kind is None:
                    continue
                for e in line.events:
                    out.append({"plane": plane.name, "kind": kind,
                                "name": e.name, "start_ns": e.start_ns,
                                "dur_ns": e.duration_ns})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out.append({"plane": "host", "kind": "host",
                                    "name": e.name, "start_ns": e.start_ns,
                                    "dur_ns": e.duration_ns})
    return out


def opcode(name: str) -> str:
    """HLO opcode of an op event ("fusion", "custom-call", "copy-start",
    ...); the event name itself where it is no HLO instruction."""
    m = INSTR.match(name)
    if not m:
        return name
    op = OPCODE.search(" " + m.group(2))
    return op.group(1) if op else re.sub(r"\.\d+$", "", m.group(1))


def kernel_of(ev: dict):
    """The Pallas kernel an op event runs, or None.  A ``tpu_custom_call``
    is booked under the name of its HLO instruction, which is the
    ``pallas_call``'s own (``%griffin_spmm.3`` -> ``griffin_spmm``,
    ``%decode_attention.7``, and whatever name a later kernel gives
    itself)."""
    name = ev["name"]
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    return re.sub(r"\.\d+$", "", INSTR.match(name).group(1))


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(events: List[dict]) -> dict:
    slices = [e for e in events if e["name"] == "bench.slice"]
    if not slices:
        raise ValueError("trace holds no bench.slice span")
    lo = slices[0]["start_ns"]
    hi = lo + slices[0]["dur_ns"]
    host = sorted((e for e in events if e["kind"] == "host"
                   and e["name"] != "bench.slice"),
                  key=lambda e: e["start_ns"])
    planes = sorted({e["plane"] for e in events if e["kind"] != "host"})
    ndev = max(1, len(planes))
    modules: Dict[str, dict] = defaultdict(
        lambda: {"s": 0.0, "n": 0.0, "kernels": defaultdict(float),
                 "collective_s": 0.0})
    ops_total: Dict[str, float] = defaultdict(float)
    busy, gaps = 0.0, []
    for plane in planes:
        mods = sorted((e for e in events if e["plane"] == plane
                       and e["kind"] == "module"),
                      key=lambda e: e["start_ns"])
        starts = [m["start_ns"] for m in mods]
        spans = []
        for m in mods:
            s, e = _clip(m["start_ns"], m["start_ns"] + m["dur_ns"], lo, hi)
            if e > s:
                modules[m["name"]]["s"] += (e - s) * 1e-9 / ndev
                modules[m["name"]]["n"] += 1.0 / ndev
        for op in (e for e in events if e["plane"] == plane
                   and e["kind"] == "op"):
            code = opcode(op["name"])
            if code.endswith("-start"):
                continue      # an async op's start spans its whole flight
            s, e = _clip(op["start_ns"], op["start_ns"] + op["dur_ns"],
                         lo, hi)
            if e <= s:
                continue
            sec = (e - s) * 1e-9 / ndev
            spans.append((s, e))
            j = bisect.bisect_right(starts, op["start_ns"]) - 1
            mod = (mods[j]["name"] if j >= 0 and op["start_ns"] <
                   mods[j]["start_ns"] + mods[j]["dur_ns"] else "(none)")
            k = kernel_of(op)
            if k is not None:
                modules[mod]["kernels"][k] += sec
            if COLLECTIVE.match(code):
                modules[mod]["collective_s"] += sec
            if code not in CONTAINERS:
                ops_total[(k or code) + " @ " +
                          re.sub(r"\(\d+\)$", "", mod)] += sec
        merged = _merge(spans)
        busy += sum(e - s for s, e in merged) * 1e-9 / ndev
        if plane == planes[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, (s + e) / 2))
    gaps = [(d, _label(host, mid)) for d, mid in
            sorted(gaps, reverse=True)[:10]]
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy, "devices": ndev,
            "modules": {k: {"s": v["s"], "n": v["n"],
                            "kernels": dict(v["kernels"]),
                            "collective_s": v["collective_s"]}
                        for k, v in modules.items()},
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": [[lab, d * 1e-9] for d, lab in gaps]}


def _label(host: List[dict], t: float) -> str:
    """The innermost host annotation covering time ``t``."""
    best = None
    for h in host:
        if h["start_ns"] > t:
            break
        if t < h["start_ns"] + h["dur_ns"]:
            best = h["name"]
    return best.split(".", 1)[1] if best else "none"


def module_sum(trace: dict, pattern: str, field: str = "s") -> float:
    return sum(m[field] for name, m in trace["modules"].items()
               if pattern in name)


def kernel_sum(trace: dict, pattern: str, kernel: str) -> float:
    return sum(m["kernels"].get(kernel, 0.0)
               for name, m in trace["modules"].items() if pattern in name)
