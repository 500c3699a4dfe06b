#!/usr/bin/env bash
# CI entry point: install, tier-1 tests, benchmark + substrate smoke checks,
# mesh-serving parity, and worktree hygiene.
#
#   scripts/ci.sh                  # full flow (editable install if pip works)
#   scripts/ci.sh tier1 docs       # selected stages only
#   SKIP_INSTALL=1 scripts/ci.sh   # offline: fall back to PYTHONPATH=src
#
# Stages (in default order) — .github/workflows/ci.yml runs the same
# stages as separate jobs, so this script IS the local mirror of CI:
#   tier1             fast default-on pytest suite (kernels split out)
#   kernel            kernel parity (interpret mode, CPU)
#   tier2             serving-engine e2e sweep (all families)
#   paged             paged KV arena: allocator/discovery units, fixed-vs-
#                     paged parity matrix, int8 tolerance gate, CLI smokes
#   serve             fused-chunk serve smoke + parity + sync budget
#   bench-regression  fresh run vs committed BENCH_serve.json invariants
#   serve-bench       fused-chunk serve benchmark smoke (CPU counts)
#   fig5              batched-sweep benchmark smoke (results cache)
#   e2e               registry models through the substrate (smoke)
#   docs              DESIGN.md citation check
#   router            SLO router: unit tier + replica-kill chaos cells +
#                     seeded 2x-overload smoke (single device)
#   mesh              8-device emulated mesh: sharded parity tier + smoke
#   chaos             8-device emulated mesh: fault-injection matrix + smoke
#   clean             worktree clean after the run (smoke CSV churn reset)
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${SKIP_INSTALL:-0}" != "1" ] && pip install -e '.[test]' 2>/dev/null; then
    echo "== installed griffin-repro (editable) with [test] extras"
    PYPATH=""
else
    echo "== pip install unavailable; using PYTHONPATH=src fallback"
    PYPATH="src"
fi
run() { PYTHONPATH="${PYPATH}${PYTHONPATH:+:$PYTHONPATH}" "$@"; }

KERNEL_TESTS="tests/test_kernels.py tests/test_sparse_a.py \
tests/test_griffin_linear.py"

stage_tier1() {
    echo "== tier-1 tests (kernel parity split into its own stage)"
    run python -m pytest -x -q \
        $(for t in $KERNEL_TESTS; do printf -- "--ignore=%s " "$t"; done)
}

stage_kernel() {
    echo "== kernel parity (interpret mode, CPU): dense / Sparse.B / Sparse.A"
    run python -m pytest -x -q $KERNEL_TESTS
}

stage_tier2() {
    echo "== tier-2: serving-engine e2e (all families, dense + sparse)"
    run python -m pytest -x -q -m tier2
}

stage_paged() {
    echo "== paged: paged KV arena (DESIGN.md Section 14) — allocator and"
    echo "==   discovery units, fixed-vs-paged token parity (tier-1 cells"
    echo "==   plus the five-family x chunk tier-2 matrix), the int8"
    echo "==   logit-tolerance gate, and serve-CLI smokes through the"
    echo "==   EngineConfig path (fp32 --parity is oracle-exact, int8 e2e)"
    run python -m pytest -x -q tests/test_paged_arena.py
    run python -m pytest -x -q -m tier2 tests/test_paged_arena.py
    run python -m repro.launch.serve --reduced --requests 6 \
        --page-size 16 --parity
    run python -m repro.launch.serve --reduced --requests 6 \
        --page-size 16 --kv-dtype int8
}

stage_serve() {
    echo "== serve smoke: fused-chunk engine, bucketed prefill, parity, and"
    echo "==   host_syncs/token <= 1/4 (asserted inside via --max-syncs-per-token)"
    run python examples/sparse_serve.py
}

stage_bench_regression() {
    echo "== bench regression: fresh serve run vs committed BENCH_serve.json"
    echo "==   (tokens/step + prefills exact, syncs/token <= recorded + 0.02)"
    run python scripts/check_bench_regression.py
}

stage_serve_bench() {
    echo "== serve bench: fused-chunk decode counts on the CPU"
    # smoke-mode run: rewrites bench_serve.csv with 16-request rows (the
    # clean stage restores it); the committed BENCH_serve.json perf record
    # is only written by `bench_serve --full --json` and never touched here
    run python -m benchmarks.bench_serve
}

stage_fig5() {
    echo "== benchmark smoke: fig5 (fast mode, batched sweep + results cache)"
    run python -m benchmarks.run --only fig5
}

stage_e2e() {
    echo "== e2e smoke: registry models through the mode-dispatched substrate"
    run python -m benchmarks.bench_e2e --smoke
}

stage_docs() {
    echo "== docs: every DESIGN.md section cited from a docstring exists"
    python scripts/check_design_refs.py
}

stage_router() {
    echo "== router: SLO admission/hedging/degradation unit tier, the"
    echo "==   replica-kill chaos cells (single device — replicas are"
    echo "==   in-process engines, no emulated mesh needed), and a seeded"
    echo "==   2x-overload smoke that must shed under a bounded queue and"
    echo "==   keep depth <= bound (DESIGN.md Section 13)"
    run python -m pytest -x -q tests/test_router.py
    run python -m pytest -x -q -m chaos tests/test_fault_tolerance.py \
        -k router
    run python examples/sparse_serve.py --replicas 2 --queue-bound 4 \
        --arrival-process bursty --rate 1 --burst-rate 8 \
        --length-dist heavy --priorities 0,1 --requests 24 \
        --slo ttft=16,slack=2 --shed-policy degrade --overload-smoke
}

stage_mesh() {
    echo "== mesh: shard-parity tier (real Pallas kernels under shard_map)"
    echo "==   + serve smokes on an emulated 8-device CPU mesh (DESIGN.md"
    echo "==   Section 10) — kernels forced on, then the decompaction-oracle"
    echo "==   fallback forced to keep the parity baseline alive"
    # subshell-scoped env: a later stage in the same invocation (e.g.
    # `ci.sh mesh bench-regression`) must not inherit the emulation
    (
        export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
        run python -m pytest -x -q -m mesh \
            tests/test_shard_map_kernels.py tests/test_mesh_serve.py
        run python examples/sparse_serve.py --mesh 2x4 --use-kernels
        run python examples/sparse_serve.py --mesh 2x2 --use-kernels \
            --spmd-fallback
    )
}

stage_chaos() {
    echo "== chaos: deterministic fault injection on an emulated 8-device"
    echo "==   CPU mesh (DESIGN.md Section 11) — kill/delay mid-trace, the"
    echo "==   engine must remesh onto the survivors and finish token-exact"
    (
        export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
        run python -m pytest -x -q -m chaos tests/test_fault_tolerance.py
        run python examples/sparse_serve.py --mesh 2x2 \
            --inject-fault kill:-1@3:decode
    )
}

stage_clean() {
    echo "== clean worktree: the smoke stages above just rewrote the two"
    echo "==   committed benchmark CSVs — restore exactly those (their"
    echo "==   pre-run content is already gone either way), then require"
    echo "==   an otherwise clean tree (stray build junk must be"
    echo "==   gitignored; intentional changes must be committed first)"
    git checkout -- benchmarks/out/bench_serve.csv \
        benchmarks/out/bench_e2e.csv 2>/dev/null || true
    if [ -n "$(git status --porcelain)" ]; then
        echo "FAIL: worktree dirty after CI run:"
        git status --short
        exit 1
    fi
    echo "worktree clean"
}

ALL_STAGES="tier1 kernel tier2 paged serve bench-regression serve-bench \
fig5 e2e docs router mesh chaos clean"
STAGES="${*:-$ALL_STAGES}"
for s in $STAGES; do
    case "$s" in
        tier1) stage_tier1 ;;
        kernel) stage_kernel ;;
        tier2) stage_tier2 ;;
        paged) stage_paged ;;
        serve) stage_serve ;;
        bench-regression) stage_bench_regression ;;
        serve-bench) stage_serve_bench ;;
        fig5) stage_fig5 ;;
        e2e) stage_e2e ;;
        docs) stage_docs ;;
        router) stage_router ;;
        mesh) stage_mesh ;;
        chaos) stage_chaos ;;
        clean) stage_clean ;;
        *) echo "unknown stage: $s (known: $ALL_STAGES)"; exit 2 ;;
    esac
done

echo "== CI OK ($STAGES)"
