"""Operations and bytes of the weight GEMMs of one decode step, from the
configuration's shapes and its pruning: what the pruned model needs, never
what a compiled program happens to do.

A GEMM ``(rows x k) @ (k x n)`` on one chip of ``chips`` that split its
output axis:
  flops   2 * rows * (kept blocks * block_k * block_n) / chips
  bytes   kept weight blocks (bf16) / chips
          + block metadata: one int32 K-block id per kept block and one
            int32 count per output tile, / chips (pruned GEMMs only)
          + the activation rows, whole on every chip (rows * k)
          + the output rows of this chip's share (rows * n / chips)
``rows`` is the decode batch, every slot of the arena.  Which GEMMs a step
runs is the configuration's block's to say (``bench.blocks``).
"""
from __future__ import annotations

from typing import Dict, List

from . import blocks
from .weights import block_plan

ITEM = 2                     # bfloat16 bytes
META = 4                     # int32 metadata entry


def gemm_shapes(conf: dict) -> List[tuple]:
    """(name, k, n, count per step) of every weight GEMM of a decode step,
    as the configuration's block lists them."""
    return blocks.of(conf).gemm_shapes(conf["arch"])


def gemm(name: str, k: int, n: int, rows: int, pruning: dict,
         chips: int) -> Dict[str, float]:
    sparse = pruning["weight_sparsity"] > 0 and name in pruning["pruned"]
    if sparse:
        p = block_plan(k, n, pruning)
        params = p["kept"] * p["bk"] * p["bn"]
        meta = (p["kept"] + p["nbn"]) * META
    else:
        params, meta = k * n, 0
    return {"params": params,
            "flops": 2.0 * rows * params / chips,
            "bytes": (params * ITEM + meta) / chips + rows * k * ITEM
            + rows * n * ITEM / chips}


def decode_step(conf: dict) -> Dict[str, float]:
    """Per chip and decode step: weight-GEMM flops and bytes, and the kept
    GEMM parameters of the whole model."""
    d = conf["deployment"]
    chips, rows = d["chips"], d["slots"]
    tot = {"params": 0.0, "flops": 0.0, "bytes": 0.0}
    for name, k, n, count in gemm_shapes(conf):
        g = gemm(name, k, n, rows, conf["pruning"], chips)
        for key in tot:
            tot[key] += count * g[key]
    return tot


def lower_bound_s(conf: dict, peak: dict) -> float:
    """Least time one chip needs for a decode step's weight GEMMs: each
    GEMM bound by compute or by bandwidth, whichever is slower."""
    d = conf["deployment"]
    t = 0.0
    for name, k, n, count in gemm_shapes(conf):
        g = gemm(name, k, n, d["slots"], conf["pruning"], d["chips"])
        t += count * max(g["flops"] / peak["bf16_flops"],
                         g["bytes"] / peak["hbm_bytes_per_s"])
    return t
