"""Fault-tolerant elastic serving tests (DESIGN.md Section 11).

Two tiers:

  - tier-1 (unmarked, runs on one device): the deterministic fault hooks —
    ``FaultInjector`` fires exactly once at the configured phase/step, the
    ``--inject-fault`` spec parser, the straggler observe/query split
    (regression: querying must not advance the eviction streak), the
    ``plan_mesh_shape`` degenerate-survivor table, checkpoint round-trips
    of live serving state (compacted ``GriffinWeights`` + promoted per-slot
    counters, leaf-exact), scheduler queue serialization through a
    checkpoint manifest, and single-device kill -> rollback-and-replay
    token parity (in-memory and via ``--snapshot-dir`` disk snapshots),
    plus a seeded hypothesis property that recovery is invariant to *when*
    the fault fires.

  - chaos (the CI ``chaos`` job:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest -m chaos``):
    the chaos matrix — kill phase {admission, prefill, decode} x mesh
    transition {2x2 -> 1x2, 2x4 -> 2x2} x weights {dense, sparse-B} must
    finish the trace with tokens identical to an *uninterrupted unsharded*
    run, exercising snapshot -> ``elastic.plan_mesh`` -> reshard -> replay
    end-to-end; plus the straggler-eviction-driven remesh, disk-snapshot
    recovery on a mesh, and the 2x2-saved -> 1x2-restored checkpoint
    resharding round-trip.  Skipped (not failed) below 8 devices.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import read_manifest, restore, save
from repro.configs import get_config
from repro.launch.mesh import mesh_spec, serve_mesh
from repro.models import build_model
from repro.runtime.elastic import (plan_mesh, plan_mesh_shape, reshard,
                                   surviving)
from repro.runtime.engine import (Attribution, Request, Scheduler,
                                  ServeEngine, _promote_arena,
                                  synthetic_trace)
from repro.runtime.fault import (DeviceLoss, FaultInjector, ReplicaFault,
                                 parse_fault_spec)
from repro.runtime.mesh_serve import MeshServeEngine, serve_shardings
from repro.runtime.paging import PageAllocator
from repro.runtime.router import RouterEngine
from repro.runtime.straggler import StragglerConfig, StragglerDetector
from repro.sparsity import sparsify_params

PRUNE = dict(block_k=16, block_n=16, unit=8)   # reduced dims (d_model 64)

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                      # pragma: no cover
    HAVE_HYPOTHESIS = False


def _needs_devices(n: int):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs {n} devices (export XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")


def _trace(cfg, n=4):
    return synthetic_trace(cfg, num_requests=n, seed=11,
                           prompt_lens=(6, 10), gen_lens=(2, 4),
                           arrival_every=1)


def _tokens(outs):
    return {r: list(map(int, o.tokens)) for r, o in outs.items()}


def _assert_trees_equal(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert [jax.tree_util.keystr(p) for p, _ in fa] == \
        [jax.tree_util.keystr(p) for p, _ in fb]
    for (p, x), (_, y) in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(p))


@pytest.fixture(scope="module")
def small():
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


@pytest.fixture(scope="module")
def reference(small):
    cfg, api, params = small
    eng = ServeEngine(api, params, num_slots=3, cache_len=24, decode_chunk=4)
    return _tokens(eng.run(_trace(cfg, 5)))


# ---------------------------------------------------------------------------
# tier-1: injector semantics
# ---------------------------------------------------------------------------

def test_fault_injector_fires_once_at_matching_phase():
    inj = FaultInjector(kill_devices=(3, 1, 3), at_step=2, phase="decode")
    inj.poll("admission", 5)            # wrong phase: never fires
    inj.poll("decode", 1)               # right phase, too early
    assert not inj.fired
    with pytest.raises(DeviceLoss) as e:
        inj.poll("decode", 4)
    assert e.value.lost == (1, 3)       # deduped, sorted ids
    assert inj.fired and inj.fired_at == 4
    inj.poll("decode", 5)               # recovery replays the tick: no re-fire


def test_fault_injector_phase_matters():
    for phase in ("admission", "prefill"):
        inj = FaultInjector(kill_devices=(0,), at_step=0, phase=phase)
        inj.poll("decode", 9)
        assert not inj.fired
        with pytest.raises(DeviceLoss):
            inj.poll(phase, 0)


def test_fault_injector_host_delay():
    inj = FaultInjector(delay_host=1, at_step=3, delay_factor=12.0)
    assert inj.host_delay(1, 2) == 1.0      # not yet due
    assert inj.host_delay(0, 5) == 1.0      # wrong host
    assert inj.host_delay(1, 3) == 12.0     # persistent from at_step on
    assert inj.host_delay(1, 9) == 12.0
    assert not inj.fired                    # delays never raise


def test_fault_injector_validation():
    with pytest.raises(ValueError):
        FaultInjector(kill_devices=(0,), phase="epilogue")
    with pytest.raises(ValueError):
        FaultInjector(kill_devices=(0,), at_step=-1)


def test_parse_fault_spec():
    s = parse_fault_spec("kill:-1@3")
    assert (s.kind, s.index, s.at_step, s.phase) == ("kill", -1, 3, "decode")
    s = parse_fault_spec("kill:2@0:prefill")
    assert (s.index, s.phase) == (2, "prefill")
    s = parse_fault_spec("delay:1@4")
    assert (s.kind, s.index, s.at_step, s.factor) == ("delay", 1, 4, 8.0)
    assert parse_fault_spec("delay:0@2:50").factor == 50.0
    for bad in ("", "kill", "kill:", "kill:1", "kill:x@3", "kill:1@x",
                "kill:1@-2", "kill:1@3:warmup", "delay:1@3:1.0",
                "delay:1@3:x", "reboot:1@3"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_fault_spec_build_resolves_device_index():
    @dataclasses.dataclass
    class Dev:
        id: int
    devs = [Dev(10), Dev(11), Dev(12)]
    inj = parse_fault_spec("kill:-1@3:prefill").build(devs)
    assert inj.kill_devices == (12,) and inj.phase == "prefill"
    inj = parse_fault_spec("delay:1@2:9").build(devs)
    assert inj.delay_host == 1 and inj.delay_factor == 9.0


# ---------------------------------------------------------------------------
# tier-1: straggler observe/query split (regression)
# ---------------------------------------------------------------------------

def test_straggler_query_is_side_effect_free():
    """The pre-split detector advanced ``flagged_streak`` inside
    ``stragglers()``, so any second query in a step double-counted the
    streak and evicted in half the configured time."""
    det = StragglerDetector(4, StragglerConfig(threshold=1.5, evict_after=3))
    for h in range(4):
        det.record(h, 1.0 if h != 2 else 3.0)
    for _ in range(10):                     # query storm: no side effects
        assert det.stragglers() == [2]
    assert list(det.flagged_streak) == [0, 0, 0, 0]
    assert det.evictions() == []
    for step in range(3):
        det.observe()                       # only observe() closes a step
        det.evictions()                     # interleaved queries stay free
        assert det.flagged_streak[2] == step + 1
    assert det.evictions() == [2]


def test_straggler_streak_resets_when_host_recovers():
    det = StragglerDetector(2, StragglerConfig(threshold=1.5, evict_after=4))
    for _ in range(3):
        det.record(0, 1.0), det.record(1, 9.0)
        det.observe()
    assert det.flagged_streak[1] == 3
    for _ in range(30):                     # EMA pulls host 1 back to par
        det.record(0, 1.0), det.record(1, 1.0)
        det.observe()
    assert det.flagged_streak[1] == 0 and det.evictions() == []


def test_straggler_rejects_empty_fleet():
    with pytest.raises(ValueError):
        StragglerDetector(0)


# ---------------------------------------------------------------------------
# tier-1: plan_mesh degenerate survivor counts
# ---------------------------------------------------------------------------

def test_plan_mesh_shape_table():
    """Pinned (n_devices, model_parallel) -> (data, model) table, including
    every degenerate case: a lone survivor, non-power-of-two survivor
    counts, and fewer survivors than the requested TP degree."""
    table = {
        (1, 1): (1, 1), (1, 4): (1, 1),     # lone survivor ignores TP ask
        (2, 2): (1, 2), (2, 1): (2, 1),
        (3, 2): (1, 2),                     # non-pow2: drop to 2 devices
        (5, 4): (1, 4), (6, 3): (2, 2),
        (7, 2): (2, 2), (7, 4): (1, 4),     # the 2x4 - 1 survivor cells
        (8, 4): (2, 4), (8, 2): (4, 2), (8, 1): (8, 1),
        (16, 4): (4, 4),
    }
    for (n, mp), want in table.items():
        assert plan_mesh_shape(n, mp) == want, (n, mp)
    for data, model in table.values():      # contract: pow2 axes
        assert data & (data - 1) == 0 and model & (model - 1) == 0
    for n, mp in ((0, 1), (1, 0), (-3, 2)):
        with pytest.raises(ValueError):
            plan_mesh_shape(n, mp)


def test_plan_mesh_builds_named_axes():
    m = plan_mesh(1, 1)
    assert m.axis_names == ("data", "model") and m.size == 1
    with pytest.raises(ValueError):
        plan_mesh(2, 2, devices=jax.devices()[:1])   # planned > provided


def test_surviving_filters_lost_ids_in_mesh_order():
    m = serve_mesh("1x1")
    dev = list(np.asarray(m.devices).flat)[0]
    assert surviving(m.devices, []) == [dev]
    assert surviving(m.devices, [dev.id]) == []


# ---------------------------------------------------------------------------
# tier-1: checkpointed serving state (satellite: save/restore roundtrip)
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_compacted_serving_state(tmp_path, small):
    """A serving snapshot — compacted ``GriffinWeights`` params plus a
    promoted (B,)-counter arena — must survive save/restore leaf-exact."""
    cfg, api, params = small
    sp = sparsify_params(params, 0.6, **PRUNE)
    cache = _promote_arena(api.init_cache(3, 16), 3)
    cache = jax.tree.map(
        lambda x: jnp.asarray(np.random.default_rng(0)
                              .standard_normal(x.shape).astype(x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else x, cache)
    state = {"params": sp, "cache": cache,
             "tokens": jnp.arange(3, dtype=jnp.int32)[:, None],
             "remaining": jnp.asarray([4, 0, 2], jnp.int32)}
    d = str(tmp_path / "ck")
    save(d, 7, state)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        state)
    out = restore(d, template, step=7)
    _assert_trees_equal(out, state)


def test_scheduler_state_dict_roundtrip():
    """Queue snapshot -> JSON -> rebuild must reproduce admission order,
    free-slot stack and per-slot countdowns exactly (extras included)."""
    sched = Scheduler(3, "continuous", max_admissions_per_step=2)
    rng = np.random.default_rng(3)
    for rid in range(6):
        extras = ({"frames": rng.standard_normal((2, 4)).astype(np.float32)}
                  if rid % 2 else None)
        sched.add(Request(rid=rid, tokens=np.arange(4 + rid, dtype=np.int32),
                          max_new_tokens=2 + rid % 3, arrival=rid // 2,
                          extras=extras))
    sched.admissions(0)                     # move some into running
    sched.emit(sched.active[0])             # and free a slot again
    d = json.loads(json.dumps(sched.state_dict()))
    clone = Scheduler.from_state_dict(d)
    assert clone.state_dict() == sched.state_dict()
    # behavioural equality: the clone admits the same requests henceforth
    for step in range(1, 5):
        a, b = sched.admissions(step), clone.admissions(step)
        assert [(s, r.rid) for s, r in a] == [(s, r.rid) for s, r in b]
    assert clone.finished == sched.finished
    assert clone.waiting_count == sched.waiting_count


def test_scheduler_state_rides_checkpoint_manifest(tmp_path):
    sched = Scheduler(2)
    sched.add(Request(rid=0, tokens=np.arange(5, dtype=np.int32),
                      max_new_tokens=3))
    d = str(tmp_path / "ck")
    save(d, 4, {"x": jnp.zeros(2)}, extra={"scheduler": sched.state_dict(),
                                           "clock": 4})
    man = read_manifest(d)                  # latest by default
    assert man["step"] == 4 and man["extra"]["clock"] == 4
    clone = Scheduler.from_state_dict(man["extra"]["scheduler"])
    assert clone.waiting_count == 1
    with pytest.raises(FileNotFoundError):
        read_manifest(str(tmp_path / "empty"))


# ---------------------------------------------------------------------------
# tier-1: single-device kill -> rollback-and-replay token parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase", ["admission", "prefill", "decode"])
def test_single_device_kill_recovers_token_exact(small, reference, phase):
    """A kill at any injection point rolls back to the tick-start snapshot
    and replays; the finished trace must equal the uninterrupted run's
    token for token (restart-in-place: one device has no survivors to
    remesh over, so recovery reuses the same device)."""
    cfg, api, params = small
    inj = FaultInjector(kill_devices=(0,), at_step=2, phase=phase)
    eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                      decode_chunk=4, fault_injector=inj)
    out = eng.run(_trace(cfg, 5))
    assert inj.fired and eng.recoveries == 1
    assert eng.recovery_log == [{"step": 2, "lost": [0],
                                 "mesh": "unsharded"}]
    assert _tokens(out) == reference


def test_snapshot_dir_disk_recovery(tmp_path, small, reference):
    """With ``snapshot_dir`` set, tick-start snapshots go through
    ``checkpoint.save`` (scheduler queues in the manifest's ``extra``) and
    recovery restores through ``checkpoint.restore`` — same tokens."""
    cfg, api, params = small
    d = str(tmp_path / "snap")
    inj = FaultInjector(kill_devices=(0,), at_step=3, phase="decode")
    eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                      decode_chunk=4, fault_injector=inj, snapshot_dir=d)
    out = eng.run(_trace(cfg, 5))
    assert eng.recoveries == 1 and _tokens(out) == reference
    man = read_manifest(d)                  # snapshots really hit disk
    sched = Scheduler.from_state_dict(man["extra"]["scheduler"])
    assert sched.num_slots == 3


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(k=st.integers(0, 5),
           phase=st.sampled_from(["admission", "prefill", "decode"]))
    @example(k=5, phase="prefill")
    def test_recovery_invariant_to_fault_step(k, phase):
        """Property: *when* the fault fires must not change the served
        tokens — every (step, phase) recovery converges to the same trace
        as the uninterrupted run.  A kill is due at the first tick at or
        after its step that runs its phase; the 5-request trace admits
        its last request at tick 4, so a prefill kill is drawn no later
        than the last admitting tick (a later one would never fire)."""
        cfg = get_config("llama3.2-1b").reduced()
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        key = "ref"
        if key not in _PROP_REF:
            eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                              decode_chunk=4)
            _PROP_REF[key] = _tokens(eng.run(_trace(cfg, 5)))
            _PROP_REF["last_admit"] = max(o.admitted
                                          for o in eng.outputs.values())
        if phase == "prefill":
            k = min(k, _PROP_REF["last_admit"])
        inj = FaultInjector(kill_devices=(0,), at_step=k, phase=phase)
        eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                          decode_chunk=4, fault_injector=inj)
        out = eng.run(_trace(cfg, 5))
        assert inj.fired and eng.recoveries == 1
        assert _tokens(out) == _PROP_REF[key]

    _PROP_REF: dict = {}


# ---------------------------------------------------------------------------
# chaos: the fault matrix on an emulated 8-device host (CI `chaos` job)
# ---------------------------------------------------------------------------

_REF_CACHE: dict = {}


def _reference8(arch, sparse):
    """Uninterrupted *unsharded* tokens per weight representation — the
    oracle every chaos cell must match (memoized across the matrix)."""
    key = (arch, sparse)
    if key not in _REF_CACHE:
        cfg = get_config(arch).reduced()
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        kw = {}
        if sparse:
            params = sparsify_params(params, 0.6, **PRUNE)
            kw = dict(use_kernels=True, interpret=True)
        eng = ServeEngine(api, params, num_slots=4, cache_len=16,
                          decode_chunk=3, **kw)
        outs = eng.run(_trace(cfg, 4))
        assert len(eng.mode_history) == 1, "mode flip would break replay"
        _REF_CACHE[key] = (api, params, _tokens(outs))
    return _REF_CACHE[key]


def _chaos_cell(spec, mp, expect, sparse, phase, at_step=3,
                snapshot_dir=None):
    api, params, ref = _reference8("llama3.2-1b", sparse)
    mesh = serve_mesh(spec)
    kill = int(np.asarray(mesh.devices).flat[-1].id)
    inj = FaultInjector(kill_devices=(kill,), at_step=at_step, phase=phase)
    eng = MeshServeEngine(api, params, mesh=mesh, num_slots=4, cache_len=16,
                          decode_chunk=3, fault_injector=inj,
                          recovery_model_parallel=mp,
                          snapshot_dir=snapshot_dir)
    out = eng.run(_trace(api.cfg, 4))
    assert inj.fired and eng.recoveries == 1, (spec, phase, sparse)
    assert mesh_spec(eng.mesh) == expect, (spec, phase, sparse)
    assert eng.recovery_log[-1]["lost"] == [kill]
    assert _tokens(out) == ref, (spec, phase, sparse)
    return eng


@pytest.mark.chaos
@_needs_devices(8)
@pytest.mark.parametrize("phase", ["admission", "prefill", "decode"])
@pytest.mark.parametrize("spec,mp,expect",
                         [("2x2", None, "1x2"), ("2x4", 2, "2x2")],
                         ids=["2x2to1x2", "2x4to2x2"])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparseB"])
def test_chaos_matrix(phase, spec, mp, expect, sparse):
    """Kill one device mid-trace at every injection point, on both mesh
    transitions, for both weight representations: the engine must remesh
    onto the survivors and finish with the uninterrupted unsharded run's
    tokens (acceptance criterion)."""
    _chaos_cell(spec, mp, expect, sparse, phase)


# ---------------------------------------------------------------------------
# chaos: router replica-kill matrix (DESIGN.md Section 13) — single-device
# replicas, so these cells need no emulated mesh
# ---------------------------------------------------------------------------

_ROUTER_ORACLE: dict = {}


def _router_oracle(api, params, reqs):
    """Uninterrupted single-engine tokens per request — the oracle every
    router chaos cell must match (greedy decode is request-independent,
    so batch-1 replays are the strongest comparison)."""
    if not _ROUTER_ORACLE:
        for r in reqs:
            eng = ServeEngine(api, params, num_slots=1, cache_len=24,
                              decode_chunk=2)
            out = eng.run([dataclasses.replace(r, arrival=0)])
            _ROUTER_ORACLE[r.rid] = list(map(int, out[r.rid].tokens))
    return _ROUTER_ORACLE


@pytest.mark.chaos
@pytest.mark.parametrize("hedge", [None, 1], ids=["retry", "hedge"])
@pytest.mark.parametrize("during,at_step",
                         [("idle", 0), ("prefill", 1), ("decode", 2)])
def test_chaos_router_replica_kill(small, during, at_step, hedge):
    """Kill replica 1 {while idle, about to prefill, mid-decode}, with and
    without hedging armed: drained in-flight requests must replay on the
    survivor (or promote to their live hedge copy), the replica must
    rejoin after recovery, and every request must finish token-identical
    to the uninterrupted single-engine oracle (acceptance criterion)."""
    cfg, api, params = small
    reqs = synthetic_trace(cfg, num_requests=6, seed=11,
                           prompt_lens=(6, 10), gen_lens=(4, 6))
    ref = _router_oracle(api, params, reqs)
    fault = ReplicaFault(replica=1, at_step=at_step, during=during,
                         recover_after=3)
    router = RouterEngine(
        lambda: ServeEngine(api, params, num_slots=2, cache_len=24,
                            decode_chunk=2),
        2, hedge_after=hedge, replica_faults=[fault])
    outs = router.run([dataclasses.replace(r) for r in reqs])
    assert fault.fired, f"{during} fault site never matched"
    kill = router.health_log[0]
    assert kill["event"] == "kill" and kill["state"] == during
    assert any(h["event"] == "rejoin" for h in router.health_log)
    assert all(h.up for h in router.replicas)
    for r in reqs:
        o = outs[r.rid]
        assert o.finished >= 0, f"rid {r.rid} never finished"
        assert list(map(int, o.tokens)) == ref[r.rid], \
            f"rid {r.rid} diverged from the single-engine oracle"
    for rid in kill["drained"]:
        assert outs[rid].attribution in (Attribution.RETRIED,
                                         Attribution.HEDGED)
    if during == "idle":
        assert kill["drained"] == [] and router.stats["retried"] == 0
    elif hedge is None:
        assert router.stats["retried"] > 0


@pytest.mark.chaos
@_needs_devices(8)
def test_chaos_straggler_eviction_drives_remesh():
    """A persistently delayed host must be evicted by the *detector* (the
    injector only inflates its step times) and routed through the same
    snapshot -> remesh -> reshard path: 2x2 -> 1x2, token parity kept."""
    api, params, ref = _reference8("llama3.2-1b", False)
    inj = FaultInjector(delay_host=1, at_step=2, delay_factor=50.0)
    det = StragglerDetector(2, StragglerConfig(evict_after=3))
    eng = MeshServeEngine(api, params, mesh=serve_mesh("2x2"), num_slots=4,
                          cache_len=16, decode_chunk=3, fault_injector=inj,
                          straggler=det)
    out = eng.run(_trace(api.cfg, 4))
    assert not inj.fired                    # no DeviceLoss was raised
    assert eng.recoveries == 1 and mesh_spec(eng.mesh) == "1x2"
    assert len(eng.recovery_log[-1]["lost"]) == 2   # host row = 2 devices
    assert _tokens(out) == ref


@pytest.mark.chaos
@_needs_devices(8)
def test_chaos_disk_snapshot_recovery_on_mesh(tmp_path):
    """Mesh recovery through the on-disk path: snapshots written with
    ``checkpoint.save`` restore through ``checkpoint.restore`` directly
    onto the *post-loss* mesh's shardings."""
    d = str(tmp_path / "snap")
    eng = _chaos_cell("2x2", None, "1x2", False, "decode", snapshot_dir=d)
    man = read_manifest(d)
    assert "scheduler" in man["extra"]
    assert eng.recovery_log[-1]["mesh"] == "1x2"


@pytest.mark.chaos
@_needs_devices(8)
def test_chaos_checkpoint_reshards_2x2_to_1x2(tmp_path):
    """Satellite: a checkpoint saved from a 2x2-sharded serving state must
    restore leaf-exactly under 1x2 shardings (params incl. compacted
    ``GriffinWeights``, arena, promoted (B,) counters)."""
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    params = sparsify_params(api.init(jax.random.PRNGKey(0)), 0.6, **PRUNE)
    cache = _promote_arena(api.init_cache(4, 16), 4)
    host = {"params": jax.tree.map(np.asarray, params),
            "cache": jax.tree.map(np.asarray, cache),
            "remaining": np.asarray([3, 1, 0, 2], np.int32)}

    def place(mesh):
        p_sh, c_sh, rep = serve_shardings(api, mesh, params, 4, 16)
        return {"params": p_sh, "cache": c_sh, "remaining": rep}

    sharded = reshard(host, place(serve_mesh("2x2")))
    d = str(tmp_path / "ck")
    save(d, 1, sharded)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        host)
    small_mesh = serve_mesh("1x2")
    out = restore(d, template, shardings=place(small_mesh))
    _assert_trees_equal(out, host)
    devs = {dv for leaf in jax.tree_util.tree_leaves(out)
            for dv in leaf.sharding.device_set}
    assert devs <= set(np.asarray(small_mesh.devices).flat)


# ---------------------------------------------------------------------------
# paged arena under faults (DESIGN.md Section 14): the page table, allocator
# state and int8 scales must ride snapshot -> rollback -> replay exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase", ["admission", "prefill", "decode"])
def test_single_device_kill_recovers_paged_token_exact(small, reference,
                                                       phase):
    """fp32 paged + kill at any phase must replay to the FIXED arena's
    reference tokens (paged fp32 is bit-exact, and recovery restores the
    pool + page table + host allocator from the tick-start snapshot)."""
    cfg, api, params = small
    inj = FaultInjector(kill_devices=(0,), at_step=2, phase=phase)
    eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                      decode_chunk=4, page_size=8, fault_injector=inj)
    assert eng._paged is not None
    out = eng.run(_trace(cfg, 5))
    assert inj.fired and eng.recoveries == 1
    assert _tokens(out) == reference
    # replay rebuilt the same page bookkeeping state machine: every page
    # is either free or parked on a dead slot awaiting the next tick-start
    # flush (the drained trace never starts another tick)
    parked = sum(len(ids) for ids in eng._slot_pages.values())
    assert eng._page_alloc.free_pages + parked == eng._paged.usable_pages
    assert set(eng._slot_pages) <= eng._dirty_slots


def test_single_device_kill_recovers_paged_int8(small):
    """int8 paged kill -> replay must match the *unfaulted int8 paged* run
    token for token: quantized pools and their per-row scales are restored
    bit-exactly, so requantization never happens on replay."""
    cfg, api, params = small

    def engine(inj=None):
        return ServeEngine(api, params, num_slots=3, cache_len=24,
                           decode_chunk=4, page_size=8, kv_dtype="int8",
                           fault_injector=inj)

    ref = _tokens(engine().run(_trace(cfg, 5)))
    inj = FaultInjector(kill_devices=(0,), at_step=3, phase="decode")
    eng = engine(inj)
    out = eng.run(_trace(cfg, 5))
    assert inj.fired and eng.recoveries == 1
    assert _tokens(out) == ref


def test_snapshot_dir_carries_paging_state(tmp_path, small, reference):
    """Disk snapshots must carry the paged host state in the manifest
    (allocator + slot->pages + dirty set) next to the device pool/table,
    and disk recovery must land on the reference tokens."""
    cfg, api, params = small
    d = str(tmp_path / "snap")
    inj = FaultInjector(kill_devices=(0,), at_step=3, phase="decode")
    eng = ServeEngine(api, params, num_slots=3, cache_len=24,
                      decode_chunk=4, page_size=8, fault_injector=inj,
                      snapshot_dir=d)
    out = eng.run(_trace(cfg, 5))
    assert eng.recoveries == 1 and _tokens(out) == reference
    man = read_manifest(d)
    paging = man["extra"]["paging"]
    assert paging["allocator"]["num_pages"] == eng._paged.num_pages
    restored = PageAllocator.from_state_dict(paging["allocator"])
    held = {i for ids in paging["slot_pages"].values() for i in ids}
    assert held <= set(paging["allocator"]["held"])
    assert restored.free_pages == eng._paged.num_pages - 1 - \
        len(paging["allocator"]["held"])


@pytest.mark.chaos
@_needs_devices(8)
@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("phase", ["admission", "prefill", "decode"])
def test_chaos_paged_mesh_kill(phase, kv_dtype):
    """Paged arena on a 2x2 mesh, kill one device at every injection point:
    the dp-sharded page pool + replicated page table must snapshot,
    reshard onto the 1x2 survivor mesh, and replay token-identical to the
    *uninterrupted unsharded paged* run with the same kv_dtype (fp32 also
    equals the fixed-arena reference by bit-exactness)."""
    api, params, fixed_ref = _reference8("llama3.2-1b", False)
    paged_eng = ServeEngine(api, params, num_slots=4, cache_len=16,
                            decode_chunk=3, page_size=8, kv_dtype=kv_dtype)
    assert paged_eng._paged is not None
    ref = _tokens(paged_eng.run(_trace(api.cfg, 4)))
    if kv_dtype == "fp32":
        assert ref == fixed_ref
    mesh = serve_mesh("2x2")
    kill = int(np.asarray(mesh.devices).flat[-1].id)
    inj = FaultInjector(kill_devices=(kill,), at_step=3, phase=phase)
    eng = MeshServeEngine(api, params, mesh=mesh, num_slots=4, cache_len=16,
                          decode_chunk=3, page_size=8, kv_dtype=kv_dtype,
                          fault_injector=inj)
    out = eng.run(_trace(api.cfg, 4))
    assert inj.fired and eng.recoveries == 1
    assert mesh_spec(eng.mesh) == "1x2"
    assert _tokens(out) == ref, (phase, kv_dtype)
