"""Find a cell's knee: the highest offered rate at which completions keep
up and the backlog does not grow through the window.

    python -m bench.sweep --workload <name> --rates 3,4,5,6 --seconds 20 \\
        --seed <n>

One set-up, then one window per rate (the cell's traffic mix with its rate
replaced), each drained before the next.  Prints one JSON line per rate:
requests offered, the backlog (due but not finished) at the middle and at
the end of the window, and the window's end-to-end numbers.  Cells are
then offered about four fifths of the knee, as a number in their traffic
file; this is how that number was found, not part of any run.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import common, stats, traffic


def backlog(reqs: dict, t: float) -> int:
    """Requests due by ``t`` and not finished by ``t``."""
    return sum(1 for r in reqs.values() if r["due"] <= t and not (
        len(r["tokens"]) == r["max_new"] and r["times"][-1] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    man = common.manifest()
    wl = common.workload(man, args.workload)
    conf = common.config_file(man, wl["config"])
    base = common.traffic_file(wl["traffic"])
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
    vocab = conf["arch"]["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    plans = [traffic.draw(dict(base, rate_rps=r), args.seed + i,
                          args.seconds, vocab) for i, r in enumerate(rates)]
    engine = loop.build(conf, args.seed)
    loop.warm(engine, base, vocab, [p for plan in plans for p in plan])
    for rate, plan in zip(rates, plans):
        rec = loop.run_window(engine, plan, args.seconds, clock,
                              drain_s=20.0)
        reqs = rec["reqs"]
        print(json.dumps({
            "rate_rps": rate, "offered": len(reqs),
            "failed": loop.failed(reqs),
            "backlog_mid": backlog(reqs, args.seconds / 2),
            "backlog_end": backlog(reqs, args.seconds),
            "queue_wait_p90_ms": common.percentile(
                stats.queue_waits_ms(reqs) or [0.0], 90),
            "compiles": rec["compiles"],
            **stats.end_to_end(reqs, args.seconds, rec["end_s"])}),
            flush=True)
        for rid in reqs:                  # leave nothing to the next rate
            engine.cancel(rid)
    print(f"memory_peak_bytes {common.peak_bytes()}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
