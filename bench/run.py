"""Run one benchmark cell once.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (the time from process start to the window) builds the cell's
model and engine through the program's own serving path with weights from
the seed, and compiles every shape the cell's traffic uses.  The window
then offers the traffic on the wall clock for ``--seconds``, drains what
is in flight, and checks the served tokens against the plain reference.
With ``--trace 1`` a few seconds in mid-window are profiled and the
per-layer metrics are printed instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
With no TPU, or fewer chips than the cell needs, it prints no result and
exits with 3.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from . import common, stats, traffic  # noqa: E402

NO_DEVICE = 3
# the profiled slice: this long, ending this long before the window
# closes; stopping the profiler stalls the host for seconds while it writes
# the trace, so the stall falls at the window's end
TRACE_S = 4.0
TRACE_END_S = 1.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_or_none(chips: int):
    """(platform, kind, count) of a TPU host with at least ``chips``
    chips, or None."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: no JAX backend: {e}", file=sys.stderr)
        return None
    d = (devs[0].platform, devs[0].device_kind, len(devs))
    if d[0] != "tpu" or d[2] < chips:
        print(f"bench: needs {chips} TPU chip(s), JAX found {d[2]} "
              f"{d[0]} device(s) ({d[1]})", file=sys.stderr)
        return None
    return d


def per_layer(man: dict, name: str, run: dict, red) -> dict:
    out = {}
    for m in man["per_layer"]:
        if "workloads" in m and name not in m["workloads"]:
            continue
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(run, red)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(man, wl, conf, mix, args, dev) -> dict:
    import jax
    from repro.configs.platform import enable_compile_cache
    from . import loop, trace

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = common.CompileClock()
    vocab = conf["arch"]["vocab_size"]
    t_import = time.perf_counter() - T0
    engine = loop.build(conf, args.seed)
    t_build = time.perf_counter() - T0
    plan = traffic.draw(mix, args.seed, args.seconds, vocab)
    loop.warm(engine, mix, vocab, plan)
    print(f"setup: imports and device {t_import:.3f} s, weights and engine "
          f"{t_build - t_import:.3f} s, warm-up "
          f"{time.perf_counter() - T0 - t_build:.3f} s, compiles so far "
          f"{clock.count} ({clock.seconds:.3f} s)", file=sys.stderr,
          flush=True)
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    sl = None
    if args.trace:
        stop = max(TRACE_S, args.seconds - TRACE_END_S)
        sl = loop.Slice(stop - TRACE_S, stop, logdir)
    # what set-up built lives as long as the window: keep it out of the
    # collector's sweeps, which otherwise walk every traced program
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T0
    with loop.GcPauses() as pauses:
        rec = loop.run_window(engine, plan, args.seconds, clock, sl)
    gc.unfreeze()
    peak = common.peak_bytes()
    engine = None
    loop.free()

    reqs = rec["reqs"]
    lag = stats.lag_ms(reqs)
    print(f"window: {len(reqs)} requests due in {args.seconds} s, "
          f"{loop.failed(reqs)} failed, loop ended at {rec['end_s']:.3f} s; "
          f"compiles inside the window {rec['compiles']}; generator lag "
          f"p50 {common.percentile(lag, 50):.3f} ms max {max(lag):.3f} ms; "
          f"setup {setup_s:.3f} s; garbage collector longest pause "
          f"{1e3 * pauses.longest:.3f} ms, {1e3 * pauses.total:.3f} ms in "
          f"all; slowest tick "
          f"{1e3 * rec['slowest_tick'][0]:.3f} ms at "
          f"{rec['slowest_tick'][1]:.3f} s", file=sys.stderr, flush=True)
    print("per request (rid ttft_ms tpot_ms): " + " ".join(
        f"{rid}:{1e3 * stats.ttft_s(r, rec['end_s']):.1f}:"
        f"{1e3 * (stats.tpot_s(r) or 0.0):.2f}" for rid, r in reqs.items()),
        file=sys.stderr, flush=True)

    result = {"correct": False, "attempted": len(reqs),
              "failed": loop.failed(reqs), "metrics": {},
              "device": {"platform": dev[0], "kind": dev[1], "count": dev[2],
                         "memory_peak_bytes": peak}}
    if args.trace:
        red = trace.reduce(trace.load(trace.newest_xplane(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
        s0, s1 = sl.stats0, sl.stats1
        run = {"conf": conf, "peak": common.peaks(dev[1]),
               "chips": wl["chips"],
               # requests due before the profiler started
               "queue_wait_ms": stats.queue_waits_ms(
                   {k: r for k, r in reqs.items() if r["due"] < sl.start_s}),
               "slice": dict(sl.counts, decode_steps=s1["decode_steps"]
                             - s0["decode_steps"])}
        result["metrics"] = per_layer(man, wl["name"], run, red)
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
        print(f"slice: {json.dumps(run['slice'])}; modules "
              f"{json.dumps(red['modules'])}", file=sys.stderr, flush=True)
    else:
        e2e = dict(stats.end_to_end(reqs, args.seconds, rec["end_s"]),
                   setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in man["end_to_end"]
                 if "workloads" not in m or wl["name"] in m["workloads"]}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if k in units}

    chk = loop.check(conf, mix, args.seed, reqs)
    limit = conf["check"]
    result["correct"] = chk["correct"]
    result["checks"] = {
        "served_gap": {"value": chk["served_gap"],
                       "limit": limit["served_gap_limit"]},
        "tokens_compared": {"value": chk["tokens"],
                            "limit": limit["min_tokens"]}}
    print(f"requests compared {chk['requests']}", file=sys.stderr)
    print(f"check served_gap {chk['served_gap']} limit <= "
          f"{limit['served_gap_limit']}", file=sys.stderr)
    print(f"check tokens_compared {chk['tokens']} limit >= "
          f"{limit['min_tokens']}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    man = common.manifest()
    wl = common.workload(man, args.workload)
    conf = common.config_file(man, wl["config"])
    mix = common.traffic_file(wl["traffic"])
    common.program_on_path()
    dev = device_or_none(wl["chips"])
    if dev is None:
        return NO_DEVICE
    result = run_cell(man, wl, conf, mix, args, dev)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
