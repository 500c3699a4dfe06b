"""The readings a cell's output limit is set from: over several seeds, the
widest gap of the program's served tokens (the lower reading) and of the
float8 control's tokens on the same positions (the upper reading).

    python -m bench.control --workload <name> --seeds 1,2,3 --seconds 20

Each seed builds the cell anew (weights from the seed), serves the cell's
own traffic for ``--seconds`` at its own rate, drains, and reads both gaps
on the same seeded sample of finished requests, each judged by the cell's
limit.  One JSON line per seed; exits with 1 if any seed's control comes
out correct (the limit then separates nothing).  The benchmark's runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import common


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    man = common.manifest()
    wl = common.workload(man, args.workload)
    conf = common.config_file(man, wl["config"])
    mix = common.traffic_file(wl["traffic"])
    common.program_on_path()
    from . import run
    dev = run.device_or_none(wl["chips"])
    if dev is None:
        return run.NO_DEVICE
    from repro.configs.platform import enable_compile_cache
    enable_compile_cache()
    import jax
    from . import loop
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = common.CompileClock()
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = loop.cycle(conf, mix, seed, args.seconds, clock, control=True)
        print(json.dumps(dict(rec["check"], seed=seed,
                              attempted=len(rec["reqs"]),
                              failed=loop.failed(rec["reqs"]),
                              compiles_in_window=rec["compiles"])),
              flush=True)
        if rec["check"]["control_correct"]:
            passed.append(seed)
    if passed:
        print(f"control came out correct on seeds {passed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
