"""Serving driver: continuous-batching engine over the jitted serve fns.

Demonstrates the paper's hybrid execution at the serving layer
(DESIGN.md Section 8): weights are block-pruned offline (Sparse.B
preprocessing, optionally compacted into ``GriffinWeights`` with
``--use-kernels``), the engine measures the workload category at runtime,
re-invokes ``core.hybrid.select_mode`` and decodes a mixed prompt/gen-length
request trace with per-slot admission/eviction over a fixed KV arena.  The
jitted prefill/decode fns and shardings come from
``runtime.serve.jit_serve_fns`` on the planned mesh.

On CPU this drives a reduced config (examples/sparse_serve.py, the
scripts/ci.sh serve stage); on TPU the same code serves the full
configs, which ``chip_smoke.py`` at the repository root drives through
``prepare`` / ``build_engine`` / ``parity_mismatches``.  ``--parity``
replays every request through the batch-1 ``greedy_generate`` oracle and
asserts token-identical output.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.platform import enable_compile_cache, kernel_interpret
from repro.models import build_model
from repro.launch.mesh import mesh_spec, serve_mesh
from repro.runtime import slo
from repro.runtime.config import EngineConfig
from repro.runtime.elastic import plan_mesh
from repro.runtime.engine import ServeEngine, synthetic_trace
from repro.runtime.fault import parse_fault_spec
from repro.runtime.mesh_serve import MeshServeEngine, init_params_sharded
from repro.runtime.router import RouterEngine
from repro.runtime.serve import greedy_generate, jit_serve_fns
from repro.runtime.slo import DegradationConfig
from repro.runtime.straggler import StragglerConfig, StragglerDetector
from repro.sparsity import sparsify_params
from repro.tuning import load_plan


def _lens(spec: str):
    return tuple(int(x) for x in spec.split(",") if x)


def _parse_slo(spec: str):
    """``--slo`` spec: comma-separated ``ttft=<ticks>`` (first-token
    deadline) and ``slack=<factor>`` (completion deadline = slack x the
    request's own expected service).  Either half may be omitted."""
    ttft, slack = None, None
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        if k == "ttft":
            ttft = int(v)
        elif k == "slack":
            slack = float(v)
        else:
            raise ValueError(f"--slo {spec!r}: unknown key {k!r} "
                             "(known: ttft, slack)")
    return ttft, slack


def _fault_hooks(args, devices, num_hosts):
    """(injector, detector) from ``--inject-fault`` (DESIGN.md Section 11);
    a delay spec also arms a straggler detector so the eviction path — not
    the injector — drives the recovery."""
    if not args.inject_fault:
        return None, None
    spec = parse_fault_spec(args.inject_fault)
    injector = spec.build(devices)
    detector = None
    if spec.kind == "delay":
        detector = StragglerDetector(
            num_hosts, StragglerConfig(evict_after=args.evict_after))
    return injector, detector


def build_engine(api, params, args, mesh, plan=None, econf=None) -> ServeEngine:
    """Engine from an ``EngineConfig`` (runtime.config) — the one
    construction path for both the unsharded and mesh-parallel engines.
    ``econf=None`` derives it from the CLI namespace (every flag explicit);
    a still-unset ``arena.cache_len`` falls back to the trace-driven bound
    (``EngineConfig.derive_cache_len``, the single source of truth the old
    duplicated derivations collapsed onto)."""
    if econf is None:
        econf = EngineConfig.from_args(args)
    if econf.arena.cache_len is None:
        econf = econf.with_fields(cache_len=EngineConfig.derive_cache_len(
            _lens(args.prompt_lens), _lens(args.gen_lens),
            getattr(args, "length_dist", "choice")))
    econf = econf.replace(kernels=dataclasses.replace(
        econf.kernels,
        # kernels imply interpret lowering on CPU (configs.platform)
        interpret=econf.kernels.use_kernels and kernel_interpret()))
    if econf.mesh:
        # mesh-parallel path (DESIGN.md Section 10): params model-sharded,
        # arena slot/head-sharded, per-Mode jits carry explicit shardings.
        smesh = serve_mesh(econf.mesh)
        injector, detector = _fault_hooks(
            args, list(smesh.devices.flat), smesh.devices.shape[0])
        return MeshServeEngine(api, params, mesh=smesh, config=econf,
                               fault_injector=injector, straggler=detector,
                               plan=plan)
    injector, detector = _fault_hooks(args, jax.devices(), 1)
    fns = None
    if econf.arena.page_size is None:
        # the sharding-annotated serve fns assume the fixed-arena cache
        # tree; paged engines trace through the default opaque-cache fns
        ns, cl = econf.arena.num_slots, econf.arena.cache_len
        dc = econf.sched.decode_chunk
        fns = lambda: jit_serve_fns(api, mesh, ns, cl, params=params,
                                    decode_chunk=dc)
    return ServeEngine(api, params, config=econf, fns_factory=fns,
                       fault_injector=injector, straggler=detector, plan=plan)


def _print_slo(rows, summary) -> None:
    """Per-request SLO attainment table + the aggregate latency summary
    (virtual ticks — runtime.slo's recorded deviation from wall clock)."""
    print("per-request SLO attainment (virtual ticks):")
    for r in rows:
        mark = {True: "ok", False: "MISS", None: "-"}[r["attained"]]
        print(f"  rid {r['rid']:>3} prio {r['priority']} "
              f"ttft {r['ttft'] if r['ttft'] is not None else '-':>4} "
              f"done {r['completion'] if r['completion'] is not None else '-':>4} "
              f"tokens {r['tokens']:>3} {r['attribution']:<8} {mark}")
    print(f"SLO summary: {summary['completed']}/{summary['requests']} "
          f"completed, {summary['shed']} shed, "
          f"ttft p50/p99 {summary['ttft_p50']}/{summary['ttft_p99']}, "
          f"itl p50/p99 {summary['itl_p50']}/{summary['itl_p99']}, "
          f"attainment {summary['slo_attainment']}")


def _run_router(api, params, args, mesh, cfg, fam_plan, reqs,
                econf=None) -> None:
    """Multi-replica path (DESIGN.md Section 13): N engines behind the
    SLO-aware router.  A 'replica:' --inject-fault spec is consumed at
    the router level; kill/delay specs keep arming replica 0's internal
    recovery path as usual."""
    replica_faults = []
    if args.inject_fault:
        spec = parse_fault_spec(args.inject_fault)
        if spec.kind == "replica":
            replica_faults = [spec.build_replica()]
            args.inject_fault = None

    engines = []     # build eagerly so replica 0 reports its config once

    def make_engine():
        eng = build_engine(api, params, args, mesh, plan=fam_plan,
                           econf=econf)
        engines.append(eng)
        return eng

    bound = args.queue_bound or None
    degradation = None
    if args.shed_policy == "none":
        bound = None
    elif bound is None:
        bound = 2 * args.slots * args.replicas
    if args.shed_policy == "degrade":
        degradation = DegradationConfig()
    router = RouterEngine(make_engine, args.replicas,
                          queue_bound=bound,
                          hedge_after=args.hedge_ms or None,
                          degradation=degradation,
                          replica_faults=replica_faults)
    e0 = router.replicas[0].engine
    print(f"router: {args.replicas} replicas x {args.slots} slots, "
          f"queue bound {bound or 'unbounded'}, "
          f"shed policy {args.shed_policy}, "
          f"hedge after {args.hedge_ms or 'off'}, "
          f"weight sparsity {e0.b_sparsity:.2f} -> mode {e0.mode.value}")

    t0 = time.time()
    outs = router.run(reqs)
    dt = time.time() - t0
    toks = sum(len(o.tokens) for o in outs.values())
    print(f"routed {len(reqs)} requests / {toks} tokens in {dt:.2f}s "
          f"over {router.clock} virtual ticks; "
          f"stats {router.stats}, max queue depth "
          f"{router.max_queue_depth}"
          + (f", ladder history {router.ladder.history}"
             if router.ladder else ""))
    if replica_faults:
        print(f"replica fault log: {router.health_log}")
        assert router.stats["completed"] + router.stats["shed"] >= len(reqs), \
            "router fault run left requests unaccounted"

    rows = slo.request_rows(outs, reqs)
    _print_slo(rows, slo.latency_summary(rows))

    if args.overload_smoke:
        assert bound is not None, "--overload-smoke needs a bounded queue"
        assert router.max_queue_depth <= bound, (
            f"queue depth {router.max_queue_depth} exceeded bound {bound}")
        assert router.stats["shed"] > 0, (
            "overload trace shed nothing — not actually overloaded?")
        print(f"overload smoke OK: depth {router.max_queue_depth} <= "
              f"{bound}, shed {router.stats['shed']}")

    if args.parity:
        eng = router.up_replicas[0].engine
        if any(len(e.mode_history) > 1 for e in engines if e is not None):
            print("parity SKIPPED: execution mode changed mid-run")
            return
        checked, bad = parity_mismatches(eng, api, reqs, outs)
        assert not bad, ("request {} diverged from greedy oracle at token "
                         "{} (engine {}, oracle {})".format(*bad[0]))
        print(f"parity OK: {checked} completed requests token-identical "
              "to greedy_generate")


def parity_mismatches(engine: ServeEngine, api, reqs, outs):
    """Replay every finished request through the batch-1
    ``greedy_generate`` oracle, on the engine's own placed params and
    under its Mode scope: prefill through the engine's jitted prefill (the
    same padded computation it admitted with), decode through a fresh
    batch-1 jit traced here, inside the scope.  Returns ``(checked,
    mismatches)``: each mismatch is ``(rid, index of the first token that
    differs, engine token, oracle token)`` (a token of -1 where one side
    ended early)."""
    checked, bad = 0, []
    with engine._scope():
        fns = (engine._fns()[0],
               jax.jit(lambda p, c, t: api.decode_step(p, c, t)))
        for r in reqs:
            o = outs.get(r.rid)
            if o is None or o.finished < 0:
                continue
            ref = np.asarray(greedy_generate(
                api, engine.params, r.as_batch(), steps=r.max_new_tokens,
                cache_len=engine.cache_len,
                prompt_bucket=engine.bucket_for(r.prompt_len), fns=fns)[0])
            got = np.asarray(o.tokens)
            checked += 1
            if not np.array_equal(got, ref):
                n = min(len(got), len(ref))
                diff = np.flatnonzero(got[:n] != ref[:n])
                at = int(diff[0]) if diff.size else n
                bad.append((r.rid, at,
                            int(got[at]) if at < len(got) else -1,
                            int(ref[at]) if at < len(ref) else -1))
    return checked, bad


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI — ``main``'s and ``chip_smoke.py``'s one parser."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="EngineConfig JSON (runtime.config.EngineConfig"
                         ".to_json): the file sets the baseline; CLI flags "
                         "set to non-default values override it")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=None,
                    help="KV arena length per slot (default: longest "
                         "prompt + longest generation + 1)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="activate the paged KV arena (DESIGN.md Section "
                         "14): power-of-two tokens per page; default keeps "
                         "the fixed num_slots x cache_len arena")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical page-pool size (default: fixed-arena "
                         "capacity + the DUMP page)")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8"), default="fp32",
                    help="paged KV page dtype: int8 stores quantized pages "
                         "with per-token-row scales (gated logit tolerance; "
                         "fp32 pages stay token-exact)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", default="8,16,32")
    ap.add_argument("--gen-lens", default="4,8,16")
    ap.add_argument("--arrival-every", type=int, default=0)
    ap.add_argument("--arrival-process", choices=("fixed", "bursty"),
                    default="fixed",
                    help="'bursty' draws Markov-modulated arrival gaps "
                         "(seeded, replayable) instead of the fixed "
                         "--arrival-every stagger")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="bursty calm-state arrival rate (requests/tick)")
    ap.add_argument("--burst-rate", type=float, default=4.0,
                    help="bursty burst-state arrival rate (requests/tick)")
    ap.add_argument("--length-dist", choices=("choice", "heavy"),
                    default="choice",
                    help="'heavy' draws Pareto generation lengths (tail "
                         "stragglers) instead of a uniform choice over "
                         "--gen-lens")
    ap.add_argument("--priorities", default="0",
                    help="comma-separated priority classes drawn per "
                         "request (0 = most important)")
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--use-kernels", action="store_true",
                    help="compact pruned weights into GriffinWeights and "
                         "execute the Sparse.B kernels (interpret on CPU); "
                         "default keeps the pruned-dense twin on plain jnp")
    ap.add_argument("--policy", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--measure-every", type=int, default=8)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="fused decode steps per host round-trip (1 = the "
                         "per-step PR 3 hot path)")
    ap.add_argument("--max-syncs-per-token", type=float, default=0.0,
                    help="assert host_syncs/token <= this after the run "
                         "(0 disables; the scripts/ci.sh serve stage "
                         "uses 0.25)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve mesh-parallel on a data x model device mesh "
                         "(e.g. 2x4; needs D*M devices — on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=8).  '1x1' is the single-device special case; "
                         "default keeps the unsharded engine")
    ap.add_argument("--spmd-fallback", action="store_true",
                    help="serve >1 meshes through the decompaction oracle "
                         "instead of the shard_map'd Pallas kernels (the "
                         "parity baseline; scripts/ci.sh smokes it to keep "
                         "the oracle alive)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="tuned kernel plan JSON (repro.launch.autotune, "
                         "DESIGN.md Section 12): this model family's entry "
                         "steers weight-compaction granularity and Mode-"
                         "selection thresholds; token output is unchanged "
                         "by construction")
    ap.add_argument("--parity", action="store_true",
                    help="assert engine tokens == greedy_generate per "
                         "request")
    ap.add_argument("--inject-fault", default=None, metavar="SPEC",
                    help="deterministic chaos (DESIGN.md Section 11): "
                         "'kill:<dev>@<step>[:<phase>]' raises a DeviceLoss "
                         "for mesh device index <dev> at engine step <step> "
                         "(phase admission|prefill|decode, default decode); "
                         "'delay:<host>@<step>[:<factor>]' inflates one "
                         "data-row's step times until the straggler "
                         "detector evicts it.  Either way the engine "
                         "snapshots, remeshes onto the survivors and "
                         "finishes the trace token-exactly")
    ap.add_argument("--snapshot-dir", default=None,
                    help="write tick-start snapshots through "
                         "checkpoint.save here and recover via "
                         "checkpoint.restore (default keeps snapshots "
                         "in host memory)")
    ap.add_argument("--remesh-model-parallel", type=int, default=None,
                    help="TP degree cap for the post-loss mesh "
                         "(default: keep the current model-axis size)")
    ap.add_argument("--evict-after", type=int, default=3,
                    help="straggler eviction streak for delay faults")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="serve through the SLO-aware multi-replica "
                         "router (DESIGN.md Section 13): N engines behind "
                         "one bounded-EDF admission queue; 0 keeps the "
                         "single-engine path.  'replica:<i>@<tick>"
                         "[:<during>[:<recover>]]' --inject-fault specs "
                         "kill whole replicas at the router level")
    ap.add_argument("--queue-bound", type=int, default=0,
                    help="router admission-queue bound (0 = unbounded "
                         "baseline: never sheds for capacity)")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="attach virtual-tick SLOs to the trace: "
                         "'ttft=<ticks>,slack=<factor>' (either half "
                         "optional); deadlines drive router EDF admission "
                         "and the attainment summary")
    ap.add_argument("--hedge-ms", type=int, default=0,
                    help="router tail-latency hedge: a dispatched request "
                         "with no first token after this many virtual "
                         "ticks is re-dispatched to a second replica and "
                         "the loser cancelled (0 = off)")
    ap.add_argument("--shed-policy", choices=("none", "shed", "degrade"),
                    default="shed",
                    help="router overload response: 'none' = unbounded "
                         "queue (the baseline failure mode), 'shed' = "
                         "bounded queue only, 'degrade' = bounded queue + "
                         "the pressure ladder (chunk cap -> cheaper Mode "
                         "-> priority shed)")
    ap.add_argument("--overload-smoke", action="store_true",
                    help="assert the router stayed bounded: "
                         "max_queue_depth <= --queue-bound and shed "
                         "count > 0 (the CI overload stage)")
    return ap


@dataclasses.dataclass
class Prepared:
    """What ``main`` serves from: the parsed flags and resolved engine
    config, the model, its params (block-pruned and compacted when
    ``--sparsity`` asks, placed in the serving layout when ``--mesh``
    asks), the kernel plan and the request trace."""
    args: argparse.Namespace
    econf: EngineConfig
    cfg: object
    api: object
    params: object
    fam_plan: object
    mesh: object
    reqs: list


def prepare(argv=None) -> Prepared:
    """Parse ``argv`` and build everything up to the engine."""
    ap = build_parser()
    args = ap.parse_args(argv)
    econf = EngineConfig.from_args(
        args, defaults={d: ap.get_default(d) for d in vars(args)})
    if econf.arena.cache_len is None:
        econf = econf.with_fields(cache_len=EngineConfig.derive_cache_len(
            _lens(args.prompt_lens), _lens(args.gen_lens), args.length_dist))
    # a --config file may have set fields the helpers below still read off
    # the namespace; the resolved config is authoritative either way
    args.slots = econf.arena.num_slots
    args.decode_chunk = econf.sched.decode_chunk
    args.use_kernels = econf.kernels.use_kernels
    args.mesh = econf.mesh
    args.replicas = econf.router.replicas
    args.queue_bound = econf.router.queue_bound or 0
    args.hedge_ms = econf.router.hedge_after or 0
    args.shed_policy = econf.router.shed_policy
    if args.inject_fault is None:
        args.inject_fault = econf.fault.inject
    if args.plan is None:
        args.plan = econf.kernels.plan

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build_model(cfg)
    mesh = plan_mesh(len(jax.devices()), args.model_parallel)
    key = jax.random.PRNGKey(0)
    params = (init_params_sharded(api, serve_mesh(econf.mesh), key)
              if econf.mesh else api.init(key))

    fam_plan = None
    if args.plan:
        fam_plan = load_plan(args.plan).family(cfg.family)
        if fam_plan is None:
            print(f"plan {args.plan} has no entry for family "
                  f"{cfg.family!r}; serving with defaults")

    if args.sparsity > 0:
        # Sparse.B preprocessing: offline block pruning of the GEMM weights
        prune = (dict(block_k=16, block_n=16, unit=8) if args.reduced
                 else dict())
        params = sparsify_params(params, args.sparsity,
                                 compact=args.use_kernels, plan=fam_plan,
                                 **prune)

    ttft_slo, slack_slo = _parse_slo(args.slo) if args.slo else (None, None)
    max_gen = None
    if args.length_dist == "heavy":
        # heavy tails must still fit the fixed cache arena
        max_gen = EngineConfig.heavy_gen_cap(_lens(args.gen_lens))
    reqs = synthetic_trace(cfg, num_requests=args.requests, seed=1,
                           prompt_lens=_lens(args.prompt_lens),
                           gen_lens=_lens(args.gen_lens),
                           arrival_every=args.arrival_every,
                           arrival_process=args.arrival_process,
                           rate=args.rate, burst_rate=args.burst_rate,
                           length_dist=args.length_dist, max_gen=max_gen,
                           priorities=_lens(args.priorities),
                           deadline_slack=slack_slo, ttft_deadline=ttft_slo)
    return Prepared(args, econf, cfg, api, params, fam_plan, mesh, reqs)


def main(argv=None) -> None:
    enable_compile_cache()
    prep = prepare(argv)
    args, econf, cfg, api, params, fam_plan, mesh, reqs = (
        prep.args, prep.econf, prep.cfg, prep.api, prep.params,
        prep.fam_plan, prep.mesh, prep.reqs)
    if args.replicas > 0:
        _run_router(api, params, args, mesh, cfg, fam_plan, reqs,
                    econf=econf)
        return

    engine = build_engine(api, params, args, mesh, plan=fam_plan,
                          econf=econf)
    arena = "fixed"
    if engine._paged is not None:
        arena = (f"paged ps={engine._paged.page_size} "
                 f"x {engine._paged.num_pages} pages "
                 f"({engine._paged.kv_dtype})")
    print(f"engine: {args.slots} slots x cache_len {engine.cache_len} "
          f"({arena}), policy={econf.sched.policy}, "
          f"mesh={args.mesh or 'unsharded'}, weight sparsity "
          f"{engine.b_sparsity:.2f} -> mode {engine.mode.value}")

    t0 = time.time()
    outs = engine.run(reqs)
    dt = time.time() - t0
    toks = engine.stats["emitted"]
    syncs_per_tok = engine.stats["host_syncs"] / max(toks, 1)
    print(f"served {len(reqs)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s on {jax.default_backend()}); "
          f"{engine.stats['decode_steps']} decode steps in "
          f"{engine.stats['chunk_calls']} fused chunks "
          f"(decode_chunk={args.decode_chunk}), "
          f"{engine.stats['prefill_calls']} prefills over buckets "
          f"{sorted(engine.prefill_buckets)}, "
          f"{syncs_per_tok:.3f} host syncs/token, "
          f"mode history {[(s, m.value) for s, m in engine.mode_history]}")
    first = outs[reqs[0].rid]
    print("request 0 token ids:", np.asarray(first.tokens[:12]))

    if args.slo:
        rows = slo.request_rows(outs, reqs)
        _print_slo(rows, slo.latency_summary(rows))

    if args.inject_fault:
        assert len(outs) == len(reqs), (
            f"fault run finished {len(outs)}/{len(reqs)} requests")
        assert all(len(o.tokens) > 0 for o in outs.values()), (
            "fault run produced an empty completion")
        final = (mesh_spec(engine.mesh) if isinstance(engine, MeshServeEngine)
                 else "unsharded")
        print(f"fault injected ({args.inject_fault}): "
              f"{engine.recoveries} recoveries, log {engine.recovery_log}, "
              f"final mesh {final}; all {len(reqs)} requests completed")

    if args.max_syncs_per_token > 0:
        assert syncs_per_tok <= args.max_syncs_per_token, (
            f"host syncs/token {syncs_per_tok:.3f} exceeds "
            f"{args.max_syncs_per_token} — the fused decode path is "
            "synchronizing per step again")
        print(f"host-sync budget OK: {syncs_per_tok:.3f} <= "
              f"{args.max_syncs_per_token}")

    if args.parity:
        if engine._paged is not None and engine._paged.kv_dtype != "fp32":
            print("parity SKIPPED: int8 KV pages are gated by logit "
                  "tolerance (benchmarks), not token equality")
            return
        if len(engine.mode_history) > 1:
            # tokens emitted before a mid-run category flip came from the
            # previous mode's kernels; a single final-mode oracle replay
            # would compare across categories
            print("parity SKIPPED: execution mode changed mid-run "
                  f"({[(s, m.value) for s, m in engine.mode_history]})")
            return
        _, bad = parity_mismatches(engine, api, reqs, outs)
        assert not bad, ("request {} diverged from greedy oracle at token "
                         "{} (engine {}, oracle {})".format(*bad[0]))
        print(f"parity OK: all {len(reqs)} requests token-identical to "
              "greedy_generate (bucketed prompts, decode_chunk="
              f"{args.decode_chunk})")


if __name__ == "__main__":
    main()
