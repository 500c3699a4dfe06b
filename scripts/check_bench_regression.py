#!/usr/bin/env python
"""Serve-benchmark regression gate: replay the trace recorded in the
committed ``benchmarks/out/BENCH_serve.json`` and fail on *invariant*
drift.

The committed JSON is the perf record ``bench_serve --full --json`` wrote;
wall-clock columns in it are machine-dependent and are **not** gated — a
slow CI box must not fail the build.  What is gated is the deterministic
skeleton of the serving engine, per recorded config:

  - emitted tokens, decode steps (hence tokens/step) and prefill calls:
    exact — these change only when scheduling, the chunk-length ladder or
    prompt bucketing change behaviour;
  - host syncs/token: <= recorded + 0.02 — the fused decode path quietly
    re-synchronizing per step is exactly the regression PR 4 exists to
    prevent (DESIGN.md Section 9), while a small slack absorbs intentional
    accounting tweaks without masking a per-step sync (+1.0);
  - sharded/unsharded tok-per-step ratio: must equal the recorded ratio
    (1.0 — sharding is placement, not scheduling) whenever a sharded row
    and its unsharded twin both replay.  Wall-clock tok/s stays ungated:
    on an emulated mesh it measures GSPMD emulation, not hardware
    (bench_serve only asserts the tok/s direction when the host has a
    core per device).

  - router overload rows (``router`` section, DESIGN.md Section 13):
    shed count, max queue depth, p50/p99 TTFT, inter-token latency and
    SLO attainment — exact, because they are counted in virtual router
    ticks over the recorded seeded trace, never in wall clock; plus the
    bounded-vs-unbounded ordering asserted inside the replay itself.

  - paged-arena row (``paged`` section, DESIGN.md Section 14): peak
    concurrent slots at the equal-KV-budget comparison (fixed 4x256 vs
    the 64x16 paged pool), the >= 2x concurrency ratio, paged-fp32
    token identity with the fixed arena, and the int8 token-match
    fraction — exact over the recorded seed; the int8 teacher-forced
    logit gap must stay within the committed tolerance (a float, so it
    is bounded rather than compared with ==).

Configs whose ``mesh`` needs more devices than this process has are
skipped with a note (the CI sharded job runs with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Run from the repo root (scripts/ci.sh bench-regression stage):

  PYTHONPATH=src python scripts/check_bench_regression.py
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

SYNC_SLACK = 0.02


def check_router(rec, api, params, cache_len, cfg, n_req, factory_cache,
                 failures) -> int:
    """Replay the committed router overload rows (DESIGN.md Section 13).
    Every gated field is in virtual router ticks — deterministic given
    the recorded trace seed — so shed counts, queue depth, p50/p99 TTFT,
    inter-token latency and SLO attainment must match with ``==``
    (cpu_wall_s/ticks stay ungated).  Returns rows checked (0 = no router
    section committed)."""
    from benchmarks.bench_serve import run_router_overload

    committed = rec.get("router")
    if not committed:
        print("skip router gate: no router section in BENCH_serve.json")
        return 0
    replay = run_router_overload(api, params, cache_len, cfg, n_req,
                                 factory_cache)
    gated = ("requests", "completed", "shed", "max_queue_depth",
             "ttft_p50", "ttft_p99", "itl_p50", "itl_p99",
             "slo_attainment", "ladder_history")
    checked = 0
    for name, got in replay.items():
        want = committed.get(name)
        if want is None:
            failures.append(f"router/{name}: row missing from the "
                            "committed record — regenerate "
                            "BENCH_serve.json")
            continue
        checked += 1
        for field in gated:
            if got[field] != want[field]:
                failures.append(f"router/{name}: {field} drifted "
                                f"{want[field]} -> {got[field]}")
        print(f"router/{name}: shed={got['shed']} "
              f"depth={got['max_queue_depth']} ttft p50/p99="
              f"{got['ttft_p50']}/{got['ttft_p99']} attainment="
              f"{got['slo_attainment']} (all vs committed, exact)")
    return checked


def check_paged(rec, api, params, cfg, failures) -> int:
    """Replay the committed paged-arena row (DESIGN.md Section 14).
    ``run_paged`` self-gates the acceptance criteria (>= 2x peak
    concurrency at equal KV budget, fp32 token-exact, int8 logit gap
    within tolerance); here the replay is additionally compared field by
    field against the record — peak concurrency, emitted tokens and the
    token-identity flags are deterministic over the recorded seed, so
    they must match with ``==`` (cpu_wall_s and the float logit gap stay
    ungated beyond the recorded tolerance).  Returns rows checked (0 =
    no paged section committed)."""
    from benchmarks.bench_serve import run_paged

    committed = rec.get("paged")
    if not committed:
        print("skip paged gate: no paged section in BENCH_serve.json")
        return 0
    replay = run_paged(api, params, cfg, committed["trace"]["requests"])
    for field in ("page_size", "num_pages", "cache_len", "fixed_slots",
                  "paged_slots", "concurrency_ratio", "fp32_token_exact",
                  "int8_token_match"):
        if replay[field] != committed[field]:
            failures.append(f"paged: {field} drifted "
                            f"{committed[field]} -> {replay[field]}")
    checked = 0
    for name, got in replay["configs"].items():
        want = committed["configs"].get(name)
        if want is None:
            failures.append(f"paged/{name}: row missing from the "
                            "committed record — regenerate "
                            "BENCH_serve.json")
            continue
        checked += 1
        for field in ("slots", "peak_concurrent", "kv_rows", "emitted"):
            if got[field] != want[field]:
                failures.append(f"paged/{name}: {field} drifted "
                                f"{want[field]} -> {got[field]}")
        print(f"paged/{name}: peak={got['peak_concurrent']} "
              f"emitted={got['emitted']} (vs committed, exact)")
    if replay["int8_rel_logit_gap"] > committed["int8_tol"]:
        failures.append(
            f"paged: int8 logit gap {replay['int8_rel_logit_gap']} "
            f"exceeds the committed tolerance {committed['int8_tol']}")
    return checked


def main() -> int:
    import jax
    from benchmarks.bench_serve import build_workload, make_engine

    jpath = ROOT / "benchmarks" / "out" / "BENCH_serve.json"
    if not jpath.exists():
        print(f"FAIL: {jpath} missing — run "
              "`python -m benchmarks.bench_serve --full --json` and commit")
        return 1
    rec = json.loads(jpath.read_text())
    n_req = rec["trace"]["requests"]
    cfg, api, params, cache_len, trace = build_workload(n_req)
    # sanity: the committed record must describe the workload this repo
    # builds, otherwise "exact" comparisons are meaningless
    from benchmarks.bench_serve import GEN_LENS, PROMPT_LENS, SLOTS
    if (rec["trace"]["prompt_lens"] != list(PROMPT_LENS)
            or rec["trace"]["gen_lens"] != list(GEN_LENS)
            or rec["trace"]["slots"] != SLOTS or rec["trace"]["seed"] != 7):
        print("FAIL: committed trace parameters differ from "
              "benchmarks/bench_serve.py — regenerate BENCH_serve.json")
        return 1

    n_dev = len(jax.devices())
    failures, checked = [], 0
    factory_cache: dict = {}
    replayed_tps: dict = {}
    for name, c in rec["configs"].items():
        mesh = c.get("mesh", "1x1")
        if mesh != "1x1":
            d, m = (int(x) for x in mesh.split("x"))
            if d * m > n_dev:
                print(f"skip {name}: mesh {mesh} needs {d * m} devices, "
                      f"have {n_dev}")
                continue
        eng = make_engine(api, params, factory_cache, c["policy"],
                          cache_len, c["decode_chunk"],
                          None if mesh == "1x1" else mesh)
        outs = eng.run(trace())
        assert len(outs) == n_req and all(o.finished >= 0
                                          for o in outs.values())
        toks = eng.stats["emitted"]
        syncs_tok = eng.stats["host_syncs"] / toks
        replayed_tps[name] = toks / max(eng.stats["decode_steps"], 1)
        checked += 1

        def exact(field, got):
            if got != c[field]:
                failures.append(f"{name}: {field} drifted "
                                f"{c[field]} -> {got}")

        exact("emitted", toks)
        exact("decode_steps", eng.stats["decode_steps"])
        exact("prefill_calls", eng.stats["prefill_calls"])
        if syncs_tok > c["host_syncs_per_token"] + SYNC_SLACK:
            failures.append(
                f"{name}: host syncs/token {syncs_tok:.4f} exceeds recorded "
                f"{c['host_syncs_per_token']} + {SYNC_SLACK} — the fused "
                "decode path is synchronizing more often than the record")
        print(f"{name}: emitted={toks} decode_steps="
              f"{eng.stats['decode_steps']} syncs/token={syncs_tok:.4f} "
              f"(recorded {c['host_syncs_per_token']})")

    # sharded rows are named "<config>@<mesh>"; their deterministic perf
    # invariant vs the unsharded twin is the tok-per-step ratio
    for name, tps in sorted(replayed_tps.items()):
        if "@" not in name:
            continue
        base = name.split("@", 1)[0]
        if base not in replayed_tps:
            continue
        got = tps / replayed_tps[base]
        want = (rec["configs"][name]["tok_per_step"] /
                rec["configs"][base]["tok_per_step"])
        if abs(got - want) > 1e-9:
            failures.append(
                f"{name}: sharded/unsharded tok-per-step ratio drifted "
                f"{want:.3f} -> {got:.3f} — sharding is changing the "
                "decode schedule")
        else:
            print(f"{name}: tok-per-step ratio vs {base} = {got:.3f} "
                  f"(recorded {want:.3f})")

    router_checked = check_router(rec, api, params, cache_len, cfg,
                                  n_req, factory_cache, failures)

    paged_checked = check_paged(rec, api, params, cfg, failures)

    for f in failures:
        print("FAIL:", f)
    print(f"check_bench_regression: {checked} configs + {router_checked} "
          f"router rows + {paged_checked} paged rows replayed against "
          f"{jpath.name}, {len(failures)} drifts")
    if checked == 0:
        print("FAIL: no configs replayed")
        return 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
