"""CPU rehearsal of the benchmark, no chip needed.

    JAX_PLATFORMS=cpu python -m bench.rehearse [--seconds 4] [--compile]

1. The open loop, warm-up and check of each configuration at a tiny size
   (two layers, narrow widths, float32), with the Pallas kernels in
   interpret mode: every configuration runs through its own path, a
   four-chip one on four virtual CPU devices.
2. ``--compile``: the decode chunk and the longest prefill bucket of every
   configuration at full size, compiled for a described ``v5e:2x2`` (one
   chip, or the 1x4 mesh), with each chip's memory printed.  Nothing runs.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

from . import common


def tiny_conf(conf: dict) -> dict:
    """The configuration at a size a CPU runs in seconds: same path, same
    pruning rule, blocks of 16 x 32.  Eight layers, so the float8 control
    drifts past the cells' limit, as it does at full size (with two it
    stayed under it on some seeds)."""
    c = copy.deepcopy(conf)
    kv = 2 if conf["arch"]["num_kv_heads"] < conf["arch"]["num_heads"] else 4
    c["arch"].update(num_layers=8, d_model=128, num_heads=4,
                     num_kv_heads=kv, head_dim=32, d_ff=256, vocab_size=512,
                     dtype="float32")
    c["deployment"].update(slots=4, cache_len=256)
    pr = conf["pruning"]
    # half the blocks, not a fifth: a head this narrow (eight K blocks)
    # otherwise leaves a sixth of its column tiles without a block, and
    # the engine reads their exact-zero logits as activation sparsity and
    # switches its Mode mid-run
    c["pruning"].update(block_k=16, block_n=32,
                        unit=32 * pr["unit"] // pr["block_n"],
                        weight_sparsity=min(pr["weight_sparsity"], 0.5))
    c["check"].update(min_tokens=160)
    return c


def tiny_mix(mix: dict) -> dict:
    m = copy.deepcopy(mix)
    m["rate_rps"] = 3.0
    m["prompt_tokens"].update(median=40, min=8, max=96)
    m["output_tokens"].update(median=16, min=4, max=32)
    return m


def pairs():
    """(name, configuration, traffic) of every configuration file: with
    the traffic of its cell, or the first cell's where it has none yet."""
    man = common.manifest()
    by_conf = {w["config"]: w for w in man["workloads"]}
    first = man["workloads"][0]["traffic"]
    for path in sorted((common.BENCH / "configs").glob("*.json")):
        conf = common.load_json(path)
        wl = by_conf.get(conf["name"])
        yield (wl["name"] if wl else conf["name"], conf,
               common.traffic_file(wl["traffic"] if wl else first))


def pair(name: str):
    """(configuration, traffic) of one name :func:`pairs` yields."""
    for n, conf, mix in pairs():
        if n == name:
            return conf, mix
    raise KeyError(f"no cell or configuration {name!r}")


def loop_rehearsal(seconds: float, seed: int) -> None:
    from . import loop, stats
    clock = common.CompileClock()
    for name, conf, mix in pairs():
        conf, mix = tiny_conf(conf), tiny_mix(mix)
        rec = loop.cycle(conf, mix, seed, seconds, clock, control=True)
        e2e = stats.end_to_end(rec["reqs"], seconds, rec["end_s"])
        print(json.dumps({"cell": name, "tiny": True,
                          "attempted": len(rec["reqs"]),
                          "failed": loop.failed(rec["reqs"]),
                          "compiles_in_window": rec["compiles"],
                          "check": rec["check"], "cpu_e2e": e2e}),
              flush=True)


def compile_rehearsal() -> None:
    from . import compile_check
    for name, conf, mix in pairs():
        print(json.dumps({"cell": name,
                          **compile_check.compile_cell(conf, mix)}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    common.program_on_path()
    if args.compile:
        compile_rehearsal()
    else:
        loop_rehearsal(args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
