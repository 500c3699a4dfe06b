"""Hypothesis property sweeps over the scheduler, functional executor,
Pallas kernels and the serving engine's slot scheduler.

hypothesis is an *optional* [test] dependency (declared in pyproject.toml);
the module-level ``pytest.importorskip`` below turns its absence into a
clean skip instead of a collection error, so the tier-1 suite never
hard-fails on a minimal environment.  The deterministic seed-parametrized
variants of these sweeps live in the sibling test modules and always run.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed "
                    "(pip install -e .[test] to enable property sweeps)")
from hypothesis import given, settings, strategies as st

from repro.core.functional import execute_b_sparse, verify_schedule
from repro.core.scheduler import schedule
from repro.core.spec import CoreConfig, sparse_b
from repro.kernels import griffin_matmul, preprocess_weights
from repro.runtime.engine import Request, Scheduler, ServeEngine

CORE = CoreConfig()


@settings(max_examples=30, deadline=None)
@given(
    t=st.integers(2, 12), k0=st.sampled_from([4, 8, 16]),
    g=st.integers(1, 3), d1=st.integers(0, 4), d2=st.integers(0, 2),
    d3=st.integers(0, 2), density=st.floats(0.05, 0.95),
    seed=st.integers(0, 999),
)
def test_schedule_invariants_property(t, k0, g, d1, d2, d3, density, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((2, t, k0, g)) < density
    s = schedule(mask, d1, d2, d3, record=True)
    verify_schedule(mask, s, d1, d2, d3)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 6), k=st.integers(3, 70), n=st.integers(1, 40),
    density=st.floats(0.02, 0.9), db1=st.integers(1, 6),
    db2=st.integers(0, 2), db3=st.integers(0, 2), sh=st.booleans(),
    seed=st.integers(0, 10_000),
)
def test_b_sparse_execution_property(m, k, n, density, db1, db2, db3, sh, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k))
    b = rng.standard_normal((k, n)) * (rng.random((k, n)) < density)
    spec = sparse_b(db1, db2, db3, shuffle=sh)
    c, ops = execute_b_sparse(a, b, spec, CORE)
    np.testing.assert_allclose(c, a @ b, rtol=1e-10, atol=1e-10)
    assert ops == (b != 0).sum()


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(1, 40), kb=st.integers(2, 6), nb=st.integers(1, 5),
    block_k=st.sampled_from([8, 16]), block_n=st.sampled_from([16, 32]),
    density=st.floats(0.1, 0.9), dual=st.booleans(), seed=st.integers(0, 99),
)
def test_griffin_spmm_property(m, kb, nb, block_k, block_n, density, dual,
                               seed):
    rng = np.random.RandomState(seed)
    k, n = kb * block_k, nb * block_n
    unit = block_n // 2
    w = rng.randn(k, n).astype(np.float32)
    # zero random (block_k x unit) blocks
    keep = rng.rand(kb, n // unit) < density
    wb = w.reshape(kb, block_k, n // unit, unit).transpose(0, 2, 1, 3).copy()
    wb[~keep] = 0
    w = wb.transpose(0, 2, 1, 3).reshape(k, n)
    a = rng.randn(m, k).astype(np.float32)
    gw = preprocess_weights(w, block_k=block_k, block_n=block_n, unit=unit,
                            balance=True)
    out = griffin_matmul(jnp.asarray(a), gw, dual=dual, interpret=True)
    np.testing.assert_allclose(np.asarray(out), a @ w, rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(1, 9), kb=st.integers(2, 5), nb=st.sampled_from([2, 4, 8]),
    trim=st.integers(0, 10), density=st.floats(0.1, 0.9),
    balance=st.booleans(), dual=st.booleans(), seed=st.integers(0, 10_000),
)
def test_griffin_shard_split_invariance_property(m, kb, nb, trim, density,
                                                 balance, dual, seed):
    """The output-axis partition law behind the shard_map serving path
    (DESIGN.md Section 10): for *every* split degree dividing the N
    tiles of random block-sparse weights, running the shard-local kernel
    entry on each contiguous tile group and concatenating is bit-equal
    to the unsharded kernel — so the model-axis size never changes the
    served logits.  Single-device: the slices are cut by hand, exactly
    as ``shard_specs`` would place them."""
    from repro.kernels.griffin_spmm.ops import griffin_matmul_shard
    bk = bn = 16
    k, n = kb * bk, nb * bn - min(trim, bn - 1)
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((k, n)).astype(np.float32)
    # zero (block_k x unit) pruning blocks on the ceil grid
    keep = rng.random((kb, -(-n // 8))) < density
    w = w * np.repeat(np.repeat(keep, bk, 0), 8, 1)[:k, :n]
    a = rng.standard_normal((m, k)).astype(np.float32)
    if dual:
        a[:, : (k // 2 // bk) * bk] = 0.0        # whole zero A blocks
    gw = preprocess_weights(w, block_k=bk, block_n=bn, unit=8,
                            balance=balance)
    ref = griffin_matmul(jnp.asarray(a), gw, dual=dual, interpret=True)
    nt = gw.kidx.shape[0]
    bm = max(8, -(-m // 8) * 8)                  # griffin_matmul's grid
    ap = jnp.pad(jnp.asarray(a), ((0, bm - m), (0, gw.k - k)))
    for shards in [d for d in range(1, nt + 1) if nt % d == 0]:
        tps = nt // shards
        parts = [griffin_matmul_shard(
                     ap, gw.b_comp[:, s * tps * bn:(s + 1) * tps * bn],
                     gw.kidx[s * tps:(s + 1) * tps],
                     gw.cnt[s * tps:(s + 1) * tps], block_m=bm, block_k=bk,
                     block_n=bn, dual=dual, interpret=True)
                 for s in range(shards)]
        out = jnp.concatenate(parts, axis=1)
        if gw.inv_perm is not None:
            out = out[:, gw.inv_perm]
        np.testing.assert_array_equal(np.asarray(out[:m, :gw.n]),
                                      np.asarray(ref), err_msg=str(shards))


# ---------------------------------------------------------------------------
# serving-engine slot scheduler (runtime.engine)
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(
    num_slots=st.integers(1, 5),
    policy=st.sampled_from(["continuous", "static"]),
    max_adm=st.integers(1, 3),
    trace=st.lists(st.tuples(st.integers(0, 25),      # arrival step
                             st.integers(1, 12),      # prompt len
                             st.integers(1, 9)),      # gen len
                   min_size=1, max_size=40),
)
def test_slot_scheduler_trace_invariants(num_slots, policy, max_adm, trace):
    """Random request traces through the serving scheduler, replaying the
    engine's emission discipline (one token at admission from the prefill
    logits, one per running slot per decode tick): no request dropped or
    duplicated, slot occupancy never exceeds the pool, every emitted token
    attributed to exactly one request, and the drain terminates."""
    sched = Scheduler(num_slots, policy, max_adm)
    reqs = [Request(rid=i, tokens=np.zeros((p,), np.int32),
                    max_new_tokens=g, arrival=a)
            for i, (a, p, g) in enumerate(trace)]
    for r in reqs:
        sched.add(r)
    emitted: dict = {}
    admitted: dict = {}
    step = 0
    bound = sum(g for _, _, g in trace) + max(a for a, _, _ in trace) + \
        len(trace) + 8
    while sched.has_work():
        for slot, req in sched.admissions(step):
            assert req.arrival <= step
            admitted[req.rid] = admitted.get(req.rid, 0) + 1
            emitted[req.rid] = emitted.get(req.rid, 0) + 1
            sched.emit(slot)
        assert len(sched.running) <= num_slots
        for slot in sched.active:
            rid = sched.running[slot].rid
            emitted[rid] = emitted.get(rid, 0) + 1
            sched.emit(slot)
        step += 1
        assert step <= bound, "scheduler failed to drain"
    assert admitted == {r.rid: 1 for r in reqs}
    assert emitted == {r.rid: r.max_new_tokens for r in reqs}
    assert sorted(sched.finished) == sorted(r.rid for r in reqs)
    assert not sched.running and not sched.waiting


@settings(max_examples=60, deadline=None)
@given(
    num_slots=st.integers(1, 4),
    policy=st.sampled_from(["continuous", "static"]),
    max_adm=st.integers(1, 3),
    trace=st.lists(st.tuples(st.integers(0, 25),      # arrival step
                             st.integers(1, 9)),      # gen len
                   min_size=1, max_size=40),
)
def test_slot_scheduler_admission_order_matches_scan(num_slots, policy,
                                                     max_adm, trace):
    """The heap-based O(1) admission path must admit exactly the requests,
    slots and order the original O(waiting)-per-tick list scan produced —
    FCFS by submission over the arrived portion of the queue — under
    arbitrary (including non-monotone) arrival patterns."""

    class ScanScheduler(Scheduler):
        """Reference: the pre-heap linear-scan admission (PR 3)."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._scan_waiting = []

        def add(self, req):
            self._scan_waiting.append(req)

        def has_work(self):
            return bool(self._scan_waiting or self.running)

        def admissions(self, step):
            if self.policy == "static" and self.running:
                return []
            budget = (self.num_slots if self.policy == "static"
                      else self.max_admissions)
            out = []
            while self._free and len(out) < budget:
                i = next((j for j, r in enumerate(self._scan_waiting)
                          if r.arrival <= step), None)
                if i is None:
                    break
                req = self._scan_waiting.pop(i)
                slot = self._free.pop()
                self.running[slot] = req
                self.remaining[slot] = req.max_new_tokens
                out.append((slot, req))
            return out

    def drive(sched):
        reqs = [Request(rid=i, tokens=np.zeros((2,), np.int32),
                        max_new_tokens=g, arrival=a)
                for i, (a, g) in enumerate(trace)]
        for r in reqs:
            sched.add(r)
        admitted, step = [], 0
        while sched.has_work():
            for slot, req in sched.admissions(step):
                admitted.append((step, slot, req.rid))
                sched.emit(slot)
            for slot in sched.active:
                sched.emit(slot)
            step += 1
            assert step < 10_000
        return admitted

    assert drive(Scheduler(num_slots, policy, max_adm)) == \
        drive(ScanScheduler(num_slots, policy, max_adm))


@settings(max_examples=12, deadline=None)
@given(
    num_slots=st.integers(1, 3),
    trace=st.lists(st.tuples(st.integers(0, 6),       # arrival step
                             st.integers(1, 6),       # prompt len
                             st.integers(1, 5)),      # gen len
                   min_size=1, max_size=10),
    seed=st.integers(0, 99),
)
def test_engine_token_attribution_property(num_slots, trace, seed):
    """The full engine (fake deterministic model) on random traces: each
    request's token stream matches an isolated batch-1 replay — tokens are
    never attributed to the wrong request, whatever the slot interleaving."""
    from test_engine import fake_api

    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(1, 17, (p,), dtype=np.int32),
                    max_new_tokens=g, arrival=a)
            for i, (a, p, g) in enumerate(trace)]
    eng = ServeEngine(api, params, num_slots=num_slots, cache_len=12)
    outs = eng.run(reqs)
    assert sorted(outs) == [r.rid for r in reqs]
    for r in reqs:
        state = int(np.sum(r.tokens)) % 17
        tok = (state + 1) % 17                  # prefill-boundary emission
        expect = [tok]
        for _ in range(r.max_new_tokens - 1):
            state = (state + tok) % 17          # decode feeds the token back
            tok = (state + 1) % 17
            expect.append(tok)
        assert outs[r.rid].tokens == expect, r.rid
    assert len(eng.events) == sum(r.max_new_tokens for r in reqs)


# ---------------------------------------------------------------------------
# SLO admission + multi-replica router (runtime.slo / runtime.router,
# DESIGN.md Section 13)
# ---------------------------------------------------------------------------

_SLO_REQS = st.lists(
    st.tuples(st.one_of(st.none(), st.integers(1, 40)),   # deadline_ms
              st.integers(0, 2),                          # priority
              st.integers(1, 12),                         # prompt len
              st.integers(1, 8)),                         # gen len
    min_size=1, max_size=25)


def _slo_reqs(spec):
    return [Request(rid=i, tokens=np.zeros((p,), np.int32),
                    max_new_tokens=g, priority=pr, deadline_ms=d)
            for i, (d, pr, p, g) in enumerate(spec)]


@settings(max_examples=50, deadline=None)
@given(spec=_SLO_REQS, b1=st.integers(1, 12), extra=st.integers(0, 12))
def test_admission_shed_deterministic_and_monotone_property(spec, b1, extra):
    """For a fixed push sequence the bounded EDF queue's shed decisions
    are a pure function of the bound: two identical queues shed the same
    rids for the same reasons in the same order, capacity sheds equal
    ``max(0, feasible - bound)`` exactly, and raising the bound never
    sheds more (the AdmissionQueue docstring contract)."""
    from repro.runtime.slo import AdmissionQueue, ShedReason

    def drive(bound):
        q = AdmissionQueue(bound)
        for r in _slo_reqs(spec):
            q.push(r, now=0)
        return q

    a, b = drive(b1), drive(b1)
    assert [(e.rid, e.reason) for e in a.shed_log] == \
        [(e.rid, e.reason) for e in b.shed_log]
    infeasible = sum(1 for e in a.shed_log
                     if e.reason is ShedReason.INFEASIBLE)
    full = sum(1 for e in a.shed_log if e.reason is ShedReason.QUEUE_FULL)
    feasible = len(spec) - infeasible
    assert full == max(0, feasible - b1)
    assert a.max_depth <= b1
    wider = drive(b1 + extra)
    assert len(wider.shed_log) <= len(a.shed_log)
    assert sum(1 for e in wider.shed_log
               if e.reason is ShedReason.INFEASIBLE) == infeasible


@settings(max_examples=50, deadline=None)
@given(spec=_SLO_REQS, bound=st.one_of(st.none(), st.integers(1, 10)),
       gaps=st.lists(st.integers(0, 6), min_size=1, max_size=30))
def test_admitted_slack_never_negative_property(spec, bound, gaps):
    """Whatever the push sequence and however long entries sit queued,
    ``pop`` never hands the dispatcher infeasible work: every admitted
    entry satisfies ``now + cost <= deadline`` (slack >= 0, stale entries
    shed as EXPIRED instead), and every pushed rid is accounted exactly
    once as admitted or shed."""
    from repro.runtime.slo import AdmissionQueue

    q = AdmissionQueue(bound)
    for r in _slo_reqs(spec):
        q.push(r, now=0)
    admitted, now = [], 0
    for gap in gaps:
        now += gap
        e, _expired = q.pop(now)
        if e is None:
            break
        slack = q.slack(e, now)
        assert slack is None or slack >= 0
        admitted.append(e.rid)
    while True:
        e, _expired = q.pop(now)
        if e is None:
            break
        assert (q.slack(e, now) or 0) >= 0
        admitted.append(e.rid)
    shed = [ev.rid for ev in q.shed_log]
    assert sorted(admitted + shed) == list(range(len(spec)))
    assert len(set(admitted) & set(shed)) == 0


@settings(max_examples=10, deadline=None)
@given(
    trace=st.lists(st.tuples(st.integers(0, 3),       # arrival tick
                             st.integers(1, 5),       # prompt len
                             st.integers(1, 5)),      # gen len
                   min_size=2, max_size=8),
    replicas=st.integers(2, 3), hedge_after=st.integers(1, 3),
    seed=st.integers(0, 99),
)
def test_router_hedging_token_exact_property(trace, replicas, hedge_after,
                                             seed):
    """Hedged re-dispatch never duplicates, drops or reorders tokens:
    whatever copy wins the race, every request's stream equals the
    deterministic batch-1 replay of the fake model, and a second run of
    the same trace routes identically (DESIGN.md Section 13)."""
    from test_engine import fake_api

    from repro.runtime.router import RouterEngine

    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(1, 17, (p,), dtype=np.int32),
                    max_new_tokens=g, arrival=a)
            for i, (a, p, g) in enumerate(trace)]

    def run():
        router = RouterEngine(
            lambda: ServeEngine(api, params, num_slots=2, cache_len=12),
            replicas, hedge_after=hedge_after)
        outs = router.run([dataclasses.replace(r) for r in reqs])
        return router, outs

    r1, outs = run()
    for r in reqs:
        state = int(np.sum(r.tokens)) % 17
        tok = (state + 1) % 17
        expect = [tok]
        for _ in range(r.max_new_tokens - 1):
            state = (state + tok) % 17
            tok = (state + 1) % 17
            expect.append(tok)
        assert list(map(int, outs[r.rid].tokens)) == expect, r.rid
        assert len(outs[r.rid].token_steps) == len(expect)
    r2, outs2 = run()
    assert {k: list(map(int, o.tokens)) for k, o in outs2.items()} == \
        {k: list(map(int, o.tokens)) for k, o in outs.items()}
    assert r1.stats == r2.stats


# ---------------------------------------------------------------------------
# sparsify_params: pruning and compaction hold no hidden state
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 999), sparsity=st.floats(0.2, 0.9),
    bk=st.sampled_from([16, 32, 64]),
)
def test_sparsify_params_idempotent_property(seed, sparsity, bk):
    """Compacting the same source weights twice at any block size yields
    bit-identical GriffinWeights — no hidden state (rng, caches, mutation
    of the source tree)."""
    from repro.sparsity import sparsify_params

    rng = np.random.default_rng(seed)
    params = {"layers": [
        {"wo": rng.standard_normal((64, 64)).astype(np.float32),
         "w_up": rng.standard_normal((64, 96)).astype(np.float32)}]}
    kw = dict(block_k=bk, block_n=bk, unit=8)
    once = sparsify_params(params, sparsity, **kw)
    twice = sparsify_params(params, sparsity, **kw)
    for a, b in zip(*(l["layers"][0].values() for l in (once, twice))):
        assert (a.k, a.n, a.block_k, a.block_n) == \
            (b.k, b.n, b.block_k, b.block_n)
        assert a.block_k == bk
        for fa, fb in zip((a.b_comp, a.kidx, a.cnt, a.inv_perm),
                          (b.b_comp, b.kidx, b.cnt, b.inv_perm)):
            if fa is None or fb is None:
                assert fa is None and fb is None
            else:
                np.testing.assert_array_equal(np.asarray(fa),
                                              np.asarray(fb))
