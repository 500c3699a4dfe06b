"""Continuous-batching engine tests.

Three layers (cheap to slow):
  - ``jit_serve_fns`` regression on a 1-device mesh (the prefill jit must
    carry the dp logits sharding that used to be computed-then-dropped,
    and the fused chunk ladder must run under the same shardings);
  - engine machinery on a trivial fake ``ModelApi`` (slot reuse, event
    attribution, prompt-boundary emission, workload-category re-selection,
    fused-vs-stepwise equivalence, stale-slot measurement masking);
  - decode/prefill parity of registry families against the batch-1
    ``greedy_generate`` oracle under a chunked + bucketed matrix: engine
    tokens == greedy tokens (oracle replaying the same prompt bucket) ==
    the prefill-logits argmax at the prompt boundary.  Dense
    transformer+xlstm run tier-1; the full four-family sweep, dense AND
    block-pruned-compacted under ``sparse_execution``, is ``tier2``
    (scripts/ci.sh runs it in its own stage).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core.spec import Mode
from repro.models import ModelApi, build_model
from repro.models.common import sparse_execution
from repro.runtime.engine import (MIN_BUCKET, Request, Scheduler, ServeEngine,
                                  synthetic_trace, weight_sparsity)
from repro.runtime.serve import (greedy_generate, jit_serve_fns,
                                 make_decode_chunk_fn, pad_prompt_batch)
from repro.sparsity import sparsify_params

FAMILY_ARCHS = {
    "transformer": "llama3.2-1b",
    "moe": "mixtral-8x7b",
    "whisper": "whisper-large-v3",
    "xlstm": "xlstm-1.3b",
    "hybrid": "recurrentgemma-9b",
}
# all five families are griffin_linear-wired (the rglru hybrid joined the
# substrate with the mesh-serving PR), so every family runs the sparse
# sweep too
SPARSE_FAMILIES = sorted(FAMILY_ARCHS)
PRUNE = dict(block_k=16, block_n=16, unit=8)   # reduced dims (d_model 64)


# ---------------------------------------------------------------------------
# fake model: deterministic request-dependent next-token function
# ---------------------------------------------------------------------------

def fake_api(vocab: int = 17, zero_logits: bool = False) -> ModelApi:
    """Minimal ModelApi: cache carries a per-row running token sum; the
    next token is (state + 1) % vocab, emitted as one-hot logits (add 1.0
    everywhere when ``zero_logits=False`` so measured activation sparsity
    stays 0).  Deterministic and request-dependent, so scheduler bugs
    (wrong slot, stale cache, cross-request leaks) change the tokens."""
    base = 0.0 if zero_logits else 1.0

    def logits_of(state):
        nxt = (state[:, 0] + 1) % vocab
        return jax.nn.one_hot(nxt, vocab, dtype=jnp.float32) + base

    def init(key):
        return {"w": jnp.zeros((vocab, vocab), jnp.float32)}

    def prefill(params, batch, cache_len=None):
        toks = batch["tokens"]
        state = jnp.sum(toks, axis=-1, keepdims=True).astype(jnp.int32) % vocab
        cache = {"state": state,
                 "pos": jnp.asarray(toks.shape[1] - 1, jnp.int32)}
        return cache, logits_of(state)

    def decode_step(params, cache, token, live=None):
        state = (cache["state"] + token) % vocab
        return logits_of(state), {"state": state, "pos": cache["pos"] + 1}

    def init_cache(batch, length):
        return {"state": jnp.zeros((batch, 1), jnp.int32),
                "pos": jnp.zeros((), jnp.int32)}

    return ModelApi(cfg=get_config("llama3.2-1b").reduced(), init=init,
                    loss=lambda p, b: jnp.zeros(()), prefill=prefill,
                    decode_step=decode_step, init_cache=init_cache,
                    param_count=lambda: 0, param_count_total=lambda: 0)


def _run_greedy(api, params, req, cache_len, scope=None, bucket=None):
    if scope is None:
        return greedy_generate(api, params, req.as_batch(),
                               steps=req.max_new_tokens,
                               cache_len=cache_len, prompt_bucket=bucket)
    with scope:
        return greedy_generate(api, params, req.as_batch(),
                               steps=req.max_new_tokens,
                               cache_len=cache_len, prompt_bucket=bucket)


# ---------------------------------------------------------------------------
# jit_serve_fns regression (satellite: logits_sh threading)
# ---------------------------------------------------------------------------

def test_jit_serve_fns_run_on_one_device_mesh():
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    B, S, clen = 2, 8, 16
    prefill_jit, decode_jit, chunk_for, (p_sh, c_sh, logits_sh) = \
        jit_serve_fns(api, mesh, B, clen)
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.ones((B, S), jnp.int32)
    cache, logits = prefill_jit(params, {"tokens": toks})
    assert logits.shape == (B, cfg.vocab_size)
    # the dp logits sharding is threaded through the jit (it used to be
    # computed and dropped)
    assert logits.sharding.is_equivalent_to(logits_sh, logits.ndim)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    logits2, cache2 = decode_jit(params, cache, tok)
    assert logits2.shape == (B, cfg.vocab_size)
    assert logits2.sharding.is_equivalent_to(logits_sh, logits2.ndim)
    assert int(cache2["pos"]) == S
    # fused chunk under the same shardings: 3 steps advance pos by 3 and
    # fill a (3, B) token ring; dead rows stay out of the measurement
    cache3, logits3 = prefill_jit(params, {"tokens": toks})
    tokens = jnp.argmax(logits3, -1).astype(jnp.int32)[:, None]
    remaining = jnp.asarray([3, 0], jnp.int32)
    cache3, tokens, remaining, ring, zn, zd, kv = chunk_for(3)(
        params, cache3, tokens, remaining)
    assert ring.shape == (3, B) and ring.dtype == jnp.int32
    assert int(cache3["pos"]) == S + 2
    assert list(np.asarray(remaining)) == [0, 0]
    assert float(zd) == 3.0                     # one live row x three steps
    # no live-KV kernel here: decode_attention reads the whole arena,
    # 2 rows x one (partial) 256-block, three steps
    assert list(np.asarray(kv)) == [6, 6]
    assert chunk_for(3) is chunk_for(3)         # ladder memoized per length


def test_jit_serve_fns_shardings_follow_compacted_params():
    """GriffinWeights trees need their own specs: p_sh built from the dense
    init shapes would broadcast the parent GEMM's spec onto the metadata."""
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = sparsify_params(api.init(jax.random.PRNGKey(0)), 0.6, **PRUNE)
    prefill_jit, _, _, (p_sh, _, _) = jit_serve_fns(api, mesh, 2, 16,
                                                    params=params)
    assert jax.tree.structure(p_sh) == jax.tree.structure(
        jax.tree.map(lambda x: 0, params))
    with sparse_execution(use_kernels=False, interpret=True):
        _, logits = prefill_jit(params, {"tokens": jnp.ones((2, 8),
                                                            jnp.int32)})
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ---------------------------------------------------------------------------
# engine machinery on the fake model
# ---------------------------------------------------------------------------

def test_engine_matches_greedy_on_fake_model():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(1, 17, (int(rng.integers(2, 9)),),
                                               dtype=np.int32),
                    max_new_tokens=int(rng.integers(1, 7)),
                    arrival=int(rng.integers(0, 5))) for i in range(11)]
    eng = ServeEngine(api, params, num_slots=3, cache_len=32)
    outs = eng.run(reqs)
    assert sorted(outs) == list(range(11))
    for r in reqs:
        ref = _run_greedy(api, params, r, cache_len=32)
        assert outs[r.rid].tokens == list(np.asarray(ref[0])), r.rid


def test_engine_event_attribution_and_slot_bounds():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, tokens=np.full((4,), i + 1, np.int32),
                    max_new_tokens=3, arrival=i // 2) for i in range(8)]
    eng = ServeEngine(api, params, num_slots=2, cache_len=16)
    for r in reqs:
        eng.add(r)
    while eng.sched.has_work():
        eng.step()
        assert len(eng.sched.running) <= 2      # slot count never exceeds pool
    # every emitted token attributed to exactly one request, counts exact
    per_rid: dict = {}
    for _, rid, _ in eng.events:
        per_rid[rid] = per_rid.get(rid, 0) + 1
    assert per_rid == {r.rid: r.max_new_tokens for r in reqs}
    assert sorted(eng.sched.finished) == [r.rid for r in reqs]
    assert eng.stats["emitted"] == sum(r.max_new_tokens for r in reqs)


def test_engine_prompt_boundary_matches_prefill_logits():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    req = Request(rid=0, tokens=np.asarray([3, 1, 4], np.int32),
                  max_new_tokens=4)
    eng = ServeEngine(api, params, num_slots=1, cache_len=16)
    outs = eng.run([req])
    _, logits = api.prefill(params, {"tokens": jnp.asarray(req.tokens)[None]},
                            cache_len=16)
    assert outs[0].tokens[0] == int(jnp.argmax(logits[0]))


def test_engine_reselects_mode_from_measured_sparsity():
    """One-hot logits are almost all exact zeros: after ``measure_every``
    decode steps the measured activation sparsity crosses the category
    threshold and the engine flips DENSE -> A, re-tracing its fns."""
    api = fake_api(zero_logits=True)
    params = api.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, tokens=np.full((3,), 2, np.int32),
                    max_new_tokens=8) for i in range(2)]
    eng = ServeEngine(api, params, num_slots=2, cache_len=16,
                      measure_every=2)
    assert eng.mode == Mode.DENSE
    eng.run(reqs)
    assert eng.mode == Mode.A
    assert eng.a_measured > 0.5
    assert [m for _, m in eng.mode_history] == [Mode.DENSE, Mode.A]
    assert eng.stats["retraces"] == 2
    # declared sparsity pins the category regardless of measurement
    eng2 = ServeEngine(api, params, num_slots=2, cache_len=16,
                       a_sparsity=0.0, measure_every=2)
    eng2.run([dataclasses.replace(r) for r in reqs])
    assert eng2.mode == Mode.DENSE


def test_engine_static_policy_admits_only_on_drained_pool():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, tokens=np.full((2,), 1, np.int32),
                    max_new_tokens=4 if i % 2 else 2) for i in range(6)]
    eng = ServeEngine(api, params, num_slots=2, cache_len=8, policy="static")
    eng.run(reqs)
    # group admissions: each admission step admits a full group of 2
    steps = sorted({o.admitted for o in eng.outputs.values()})
    assert len(steps) == 3
    for s in steps:
        assert sum(1 for o in eng.outputs.values() if o.admitted == s) == 2


def test_engine_rejects_oversized_and_frameless_requests():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, num_slots=1, cache_len=8)
    with pytest.raises(ValueError):
        eng.add(Request(rid=0, tokens=np.zeros((6,), np.int32),
                        max_new_tokens=4))
    wcfg = get_config("whisper-large-v3").reduced()
    wapi = build_model(wcfg)
    weng = ServeEngine(wapi, wapi.init(jax.random.PRNGKey(0)), num_slots=1,
                       cache_len=8)
    with pytest.raises(ValueError):
        weng.add(Request(rid=1, tokens=np.zeros((2,), np.int32),
                         max_new_tokens=2))


def test_weight_sparsity_counts_gemm_leaves_only():
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    assert weight_sparsity(params) < 0.01       # dense init: no exact zeros
    pruned = sparsify_params(params, 0.75, compact=False, **PRUNE)
    assert weight_sparsity(pruned) > 0.5
    compacted = sparsify_params(params, 0.75, **PRUNE)
    assert 0.3 < weight_sparsity(compacted) <= 1.0


# ---------------------------------------------------------------------------
# fused-path regressions (stale slots, chunk ladder, prompt buckets)
# ---------------------------------------------------------------------------

def test_chunk_fn_masks_dead_rows_out_of_measurement():
    """Direct regression on the fused scan: rows with ``remaining == 0``
    (freed or never-admitted slots) must not leak their stale logits into
    the zero-fraction accumulator — the bug class the old
    ``logits[jnp.asarray(active)]`` gather guarded against."""
    api = fake_api(zero_logits=True)      # one-hot logits: zf ~ 16/17
    params = api.init(jax.random.PRNGKey(0))
    seen = []                              # live masks the steps are given

    def decode_step(params, cache, token, live=None):
        seen.append(live)
        return fake_api(zero_logits=True).decode_step(params, cache, token)

    api = dataclasses.replace(api, decode_step=decode_step)
    chunk_fn = make_decode_chunk_fn(api, 4)
    cache = {"state": jnp.asarray([[3], [9]], jnp.int32),
             "pos": jnp.zeros((2,), jnp.int32)}
    tokens = jnp.asarray([[1], [2]], jnp.int32)
    # row 1 is dead: its one-hot rows would dominate the mean if leaked
    _, _, _, _, zn, zd, _ = chunk_fn(params, cache, tokens,
                                  jnp.asarray([4, 0], jnp.int32))
    assert float(zd) == 4.0               # only row 0, all four steps
    assert 0.9 < float(zn) / float(zd) < 1.0
    # the decode step is told which rows are live (traced once, in scan)
    assert len(seen) == 1 and seen[0].shape == (2,)
    assert seen[0].dtype == jnp.bool_
    # all-dead pool: denominator 0, numerator 0 (engine skips measuring)
    _, _, _, _, zn0, zd0, _ = chunk_fn(params, cache, tokens,
                                    jnp.asarray([0, 0], jnp.int32))
    assert float(zd0) == 0.0 and float(zn0) == 0.0


def test_engine_measurement_ignores_stale_and_unadmitted_slots():
    """Engine-level twin: a 3-slot pool serving one live dense-logits
    request must stay DENSE even though the two never-admitted slots keep
    producing one-hot (zero-heavy) garbage rows every chunk."""

    vocab = 17

    def logits_of_mixed(state):
        nxt = (state[:, 0] + 1) % vocab
        onehot = jax.nn.one_hot(nxt, vocab, dtype=jnp.float32)
        # rows with state 0 (unadmitted slots never leave 0) emit bare
        # one-hot rows; live rows get a dense +1 offset
        dense = (state[:, 0] != 0).astype(jnp.float32)[:, None]
        return onehot + dense

    api = fake_api()
    api = dataclasses.replace(
        api,
        prefill=lambda params, batch, cache_len=None: (
            {"state": jnp.sum(batch["tokens"], -1, keepdims=True
                              ).astype(jnp.int32) % vocab,
             "pos": jnp.asarray(batch["tokens"].shape[1] - 1, jnp.int32)},
            logits_of_mixed(jnp.sum(batch["tokens"], -1, keepdims=True
                                    ).astype(jnp.int32) % vocab)),
        decode_step=lambda params, cache, token, live=None: (
            logits_of_mixed((cache["state"] + token) % vocab),
            {"state": (cache["state"] + token) % vocab,
             "pos": cache["pos"] + 1}))
    params = api.init(jax.random.PRNGKey(0))
    req = Request(rid=0, tokens=np.asarray([5], np.int32), max_new_tokens=9)
    eng = ServeEngine(api, params, num_slots=3, cache_len=16,
                      measure_every=2, decode_chunk=4)
    eng.run([req])
    # live row contributes ~16/17 one-hot zeros *plus* the dense offset ->
    # exactly zero zeros; stale rows would have pushed this above threshold
    assert eng.a_measured == 0.0, eng.a_measured
    assert eng.mode == Mode.DENSE
    assert [m for _, m in eng.mode_history] == [Mode.DENSE]


def test_engine_fused_and_stepwise_paths_agree():
    """`fused=False` preserves the PR 3 per-step hot path; both paths must
    produce identical per-request tokens and attribution counts."""
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    mk = lambda: [Request(rid=i,
                          tokens=rng.integers(1, 17, (int(p),), np.int32),
                          max_new_tokens=int(g), arrival=int(a))
                  for i, (p, g, a) in enumerate(
                      zip([3, 7, 2, 5, 4], [6, 1, 9, 3, 5],
                          [0, 0, 2, 3, 3]))]
    rng = np.random.default_rng(5)
    fused = ServeEngine(api, params, num_slots=2, cache_len=32,
                        decode_chunk=4).run(mk())
    rng = np.random.default_rng(5)
    stepwise = ServeEngine(api, params, num_slots=2, cache_len=32,
                           fused=False).run(mk())
    assert {r: o.tokens for r, o in fused.items()} == \
        {r: o.tokens for r, o in stepwise.items()}


def test_chunk_ladder_wastes_no_decode_steps():
    """The completion bound must account for the prefill-boundary token of
    freshly admitted slots (they owe the device one step fewer than the
    scheduler's pre-drain ``remaining`` says), and a tick whose live slots
    all owe zero decode steps must not dispatch a dead chunk."""
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, num_slots=2, cache_len=16, decode_chunk=8)
    eng.run([Request(rid=0, tokens=np.asarray([3, 1], np.int32),
                     max_new_tokens=4)])
    # prefill emits token 1; exactly 3 decode steps may run (2 + 1 ladder)
    assert eng.stats["decode_steps"] == 3, eng.stats
    assert eng.stats["emitted"] == 4
    # all-single-token admissions: prefill tokens ride the sync, no chunk
    eng2 = ServeEngine(api, params, num_slots=2, cache_len=16,
                       decode_chunk=8, max_admissions_per_step=2)
    eng2.run([Request(rid=i, tokens=np.asarray([i + 1], np.int32),
                      max_new_tokens=1) for i in range(2)])
    assert eng2.stats["decode_steps"] == 0
    assert eng2.stats["emitted"] == 2 and eng2.stats["host_syncs"] == 1


def test_chunk_ladder_is_capped_by_factory():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, num_slots=1, cache_len=16, decode_chunk=4)
    with pytest.raises(ValueError):
        eng._fns()[2](5)                      # beyond the configured ladder
    with pytest.raises(ValueError):
        eng._fns()[2](0)


def test_bucket_for_policy():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, num_slots=1, cache_len=40)
    assert eng.bucket_for(1) == MIN_BUCKET
    assert eng.bucket_for(8) == 8
    assert eng.bucket_for(9) == 16
    assert eng.bucket_for(17) == 32
    # bucket would overflow the cache -> exact-length fallback
    assert eng.bucket_for(33) is None
    off = ServeEngine(api, params, num_slots=1, cache_len=40,
                      bucket_prompts=False)
    assert off.bucket_for(9) is None
    # windowed archs cap buckets at the usable window, not the cache
    wcfg = get_config("mixtral-8x7b").reduced()   # window 32
    wapi = build_model(wcfg)
    weng = ServeEngine(wapi, wapi.init(jax.random.PRNGKey(0)), num_slots=1,
                       cache_len=64)
    assert weng.bucket_for(20) == 32
    assert weng.bucket_for(33) is None


def test_engine_bounds_prefill_shapes_on_ragged_trace():
    """Many distinct prompt lengths must collapse onto O(log cache_len)
    admitted prefill shapes — the retrace bound bucketing buys."""
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, tokens=np.full((i + 1,), 2, np.int32),
                    max_new_tokens=2) for i in range(24)]   # lens 1..24
    eng = ServeEngine(api, params, num_slots=2, cache_len=32)
    eng.run(reqs)
    assert eng.prefill_buckets <= {8, 16, 32}
    assert len(eng.prefill_buckets) == 3


# ---------------------------------------------------------------------------
# registry-family decode/prefill parity vs the greedy oracle
# ---------------------------------------------------------------------------

def _family_parity(arch: str, sparse: bool, num_requests: int = 5,
                   decode_chunk: int = 3, bucket_prompts: bool = True):
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    kw = {}
    if sparse:
        params = sparsify_params(params, 0.6, **PRUNE)
        kw = dict(use_kernels=True, interpret=True)
    reqs = synthetic_trace(cfg, num_requests=num_requests, seed=11,
                           prompt_lens=(6, 10), gen_lens=(2, 4),
                           arrival_every=1)
    cache_len = 16
    eng = ServeEngine(api, params, num_slots=2, cache_len=cache_len,
                      decode_chunk=decode_chunk,
                      bucket_prompts=bucket_prompts, **kw)
    outs = eng.run(reqs)
    # single-category run: the final-mode oracle replay below is only a
    # valid comparison when no mid-run flip occurred (real-model logits
    # have no exact zeros, so measurement cannot flip the category here)
    assert len(eng.mode_history) == 1, eng.mode_history
    for r in reqs:
        bucket = eng.bucket_for(r.prompt_len)
        if bucket_prompts:
            assert bucket is not None     # this trace must exercise buckets
        ref = _run_greedy(api, params, r, cache_len, scope=eng._scope(),
                          bucket=bucket)
        got = outs[r.rid].tokens
        assert got == list(np.asarray(ref[0])), (arch, sparse, r.rid)
        # prompt boundary: first emitted token is the prefill-logits argmax
        # of the same padded batch the engine admitted with
        with eng._scope():
            _, logits0 = api.prefill(params, r.as_batch(bucket),
                                     cache_len=cache_len)
        assert got[0] == int(jnp.argmax(logits0[0])), (arch, sparse)
    if sparse:
        assert eng.mode == Mode.B
        assert eng.b_sparsity > 0.05


@pytest.mark.parametrize("family", ["transformer", "xlstm"])
@pytest.mark.parametrize("decode_chunk", [1, 3])
def test_engine_parity_dense_fast(family, decode_chunk):
    _family_parity(FAMILY_ARCHS[family], sparse=False, num_requests=3,
                   decode_chunk=decode_chunk)


def test_engine_parity_unbucketed_exact_lengths():
    """bucket_prompts=False keeps the exact-length prefill path alive (the
    fallback for prompts whose bucket would overflow the cache)."""
    _family_parity(FAMILY_ARCHS["transformer"], sparse=False,
                   num_requests=3, bucket_prompts=False)


@pytest.mark.tier2
@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_engine_parity_dense(family):
    _family_parity(FAMILY_ARCHS[family], sparse=False)


@pytest.mark.tier2
@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_engine_parity_dense_stepwise_chunk1(family):
    _family_parity(FAMILY_ARCHS[family], sparse=False, num_requests=3,
                   decode_chunk=1)


@pytest.mark.tier2
@pytest.mark.parametrize("family", SPARSE_FAMILIES)
def test_engine_parity_sparse(family):
    _family_parity(FAMILY_ARCHS[family], sparse=True, num_requests=3)


# ---------------------------------------------------------------------------
# EngineConfig (runtime/config.py)
# ---------------------------------------------------------------------------

def test_engine_config_json_roundtrip():
    from repro.runtime.config import ArenaConfig, EngineConfig
    from repro.runtime.config import RouterConfig
    cfg = EngineConfig(arena=ArenaConfig(num_slots=8, cache_len=96,
                                         page_size=16, kv_dtype="int8"),
                       router=RouterConfig(replicas=3, queue_bound=7),
                       mesh="2x2").with_fields(decode_chunk=4)
    assert EngineConfig.from_json(cfg.to_json()) == cfg


def test_engine_config_json_rejects_unknown():
    from repro.runtime.config import EngineConfig
    with pytest.raises(ValueError):
        EngineConfig.from_json('{"nope": {}}')
    with pytest.raises(ValueError):
        EngineConfig.from_json('{"arena": {"slotz": 4}}')


def test_engine_config_with_fields_routes_and_rejects():
    from repro.runtime.config import EngineConfig
    cfg = EngineConfig().with_fields(num_slots=6, use_kernels=True,
                                     mesh="4x1")
    assert cfg.arena.num_slots == 6
    assert cfg.kernels.use_kernels is True
    assert cfg.mesh == "4x1"
    with pytest.raises(TypeError):
        EngineConfig().with_fields(slotz=6)


def test_engine_config_derive_cache_len():
    from repro.runtime.config import EngineConfig
    assert EngineConfig.derive_cache_len((8, 16, 24), (12, 112)) == 137
    # heavy tail: cap = 2 * max gen, the bench_serve workload bound
    assert EngineConfig.heavy_gen_cap((12, 112)) == 224
    assert EngineConfig.derive_cache_len((8, 16, 24), (12, 112),
                                         "heavy") == 249


def test_engine_legacy_kwargs_warn_and_match_config():
    from repro.runtime.config import ArenaConfig, EngineConfig
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    with pytest.warns(DeprecationWarning, match="EngineConfig"):
        legacy = ServeEngine(api, params, num_slots=3, cache_len=24,
                             decode_chunk=2)
    cfg = EngineConfig(arena=ArenaConfig(num_slots=3, cache_len=24)
                       ).with_fields(decode_chunk=2)
    modern = ServeEngine(api, params, config=cfg)
    assert (legacy.num_slots, legacy.cache_len) == (3, 24)
    trace = lambda: [Request(rid=i, tokens=np.arange(1, 5 + i, dtype=np.int32),
                             max_new_tokens=3) for i in range(3)]
    outs_l = legacy.run(trace())
    outs_m = modern.run(trace())
    assert {r: o.tokens for r, o in outs_l.items()} == \
           {r: o.tokens for r, o in outs_m.items()}


def test_engine_unknown_kwarg_raises():
    api = fake_api()
    params = api.init(jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match="num_slotz"):
        ServeEngine(api, params, num_slotz=3)


# ---------------------------------------------------------------------------
# spans, counters and wall stamps (runtime/spans.py)
# ---------------------------------------------------------------------------

def _stamped_trace():
    """A lone one-token request (a pure-admission tick), then chunked
    decode with admissions at three other bucket lengths."""
    return [Request(0, np.arange(1, 6, dtype=np.int32), 1, arrival=0),
            Request(1, np.arange(1, 13, dtype=np.int32), 6, arrival=0),
            Request(2, np.arange(1, 21, dtype=np.int32), 3, arrival=2),
            Request(3, np.arange(1, 4, dtype=np.int32), 9, arrival=3)]


def _stamped_engine():
    from repro.runtime.config import ArenaConfig, EngineConfig
    api = fake_api()
    cfg = EngineConfig(arena=ArenaConfig(num_slots=2, cache_len=64)
                       ).with_fields(decode_chunk=4)
    return ServeEngine(api, api.init(jax.random.PRNGKey(0)), config=cfg)


def _ticks(eng, reqs):
    for r in reqs:
        eng.add(r)
    n = 0
    while eng.sched.has_work():
        eng.step()
        n += 1
    return n


def _engine_spans(logdir):
    import glob
    import os
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("engine.")]


def test_engine_spans_nest_in_each_tick(tmp_path):
    eng = _stamped_engine()
    with jax.profiler.trace(str(tmp_path)):
        ticks = _ticks(eng, _stamped_trace())
    spans = _engine_spans(str(tmp_path))
    assert {s[0] for s in spans} == {
        "engine." + n for n in ("tick", "admit", "prefill", "insert",
                                "chunk", "sync", "emit")}
    tick = sorted(s for s in spans if s[0] == "engine.tick")
    assert len(tick) == ticks
    assert {t[3]["mode"] for t in tick} == {eng.mode.value}
    assert [t[3]["clock"] for t in tick] == sorted(t[3]["clock"]
                                                   for t in tick)
    children = [s for s in spans if s[0] != "engine.tick"]
    for c in children:
        # inside exactly one tick, and inside no other child
        assert sum(t[1] <= c[1] and c[2] <= t[2] for t in tick) == 1, c
        assert not any(o is not c and o[1] <= c[1] and c[2] <= o[2]
                       and o[0] != c[0] for o in children), c
    by = lambda n: [s[3] for s in children if s[0] == "engine." + n]
    assert sorted(a["rid"] for a in by("prefill")) == [0, 1, 2, 3]
    assert sorted(a["rid"] for a in by("insert")) == [0, 1, 2, 3]
    assert {a["rid"]: (a["prompt_len"], a["bucket"])
            for a in by("prefill")} == {0: (5, 8), 1: (12, 16),
                                        2: (20, 32), 3: (3, 8)}
    assert all(a["chunk"] in (1, 2, 4) and a["live"] >= 1
               for a in by("chunk"))
    assert sum(a["tokens"] for a in by("emit")) == eng.stats["emitted"]
    # the first tick only admits the one-token request: sync and emit, no
    # chunk
    first = [s[0] for s in children if tick[0][1] <= s[1] < tick[0][2]]
    assert sorted(first) == ["engine.admit", "engine.emit", "engine.insert",
                             "engine.prefill", "engine.sync"]
    assert len(by("sync")) == eng.stats["host_syncs"]


def test_engine_spans_add_no_host_sync(tmp_path):
    """The same trace, traced and not: the same tokens and counters, and
    the seed engine's six host syncs."""
    plain, traced = _stamped_engine(), _stamped_engine()
    _ticks(plain, _stamped_trace())
    with jax.profiler.trace(str(tmp_path)):
        _ticks(traced, _stamped_trace())
    assert plain.stats == traced.stats
    assert plain.stats["host_syncs"] == 6
    assert {r: o.tokens for r, o in plain.outputs.items()} == \
        {r: o.tokens for r, o in traced.outputs.items()}


def test_engine_counters_match_the_work():
    eng = _stamped_engine()
    reqs = _stamped_trace()
    _ticks(eng, reqs)
    st = eng.stats
    assert st["live_rows"] == st["emitted"] - st["prefill_calls"] == 15
    assert st["prefill_tokens"] == sum(r.prompt_len for r in reqs)
    assert st["prefill_padded_tokens"] == \
        sum(eng.bucket_for(r.prompt_len) for r in reqs)
    assert "idle_steps" not in st


def test_engine_stamps_in_order():
    eng = _stamped_engine()
    _ticks(eng, _stamped_trace())
    assert sorted(eng.outputs) == [0, 1, 2, 3]
    for o in eng.outputs.values():
        assert None not in (o.t_added, o.t_admitted, o.t_first)
        assert o.t_added <= o.t_admitted <= o.t_first


def test_layer_scopes_name_the_compiled_chunk_ops():
    """The fused decode chunk's ops carry the model's ``attention`` and
    ``griffin_linear`` name scopes in their op-name metadata."""
    import re
    api = build_model(get_config("stablelm-1.6b").reduced())
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.zeros((2,), x.dtype) if x.ndim == 0 else x,
        api.init_cache(2, 32)))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = jax.jit(make_decode_chunk_fn(api, 2)).lower(
        params, cache, tokens, tokens.update(shape=(2,))).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("attention", "griffin_linear"):
        assert any(f"/{scope}/" in n for n in names), scope


# ---------------------------------------------------------------------------
# live-KV decode attention (kernels/decode_attention) in the served path
# ---------------------------------------------------------------------------

def _kernel_config(num_slots=3, cache_len=256, decode_chunk=4):
    from repro.runtime.config import ArenaConfig, EngineConfig
    return EngineConfig(arena=ArenaConfig(num_slots=num_slots,
                                          cache_len=cache_len)
                        ).with_fields(decode_chunk=decode_chunk,
                                      use_kernels=True, interpret=True)


def test_engine_live_kv_kernel_keeps_every_live_token(monkeypatch):
    """The fused engine with kernels on a small transformer: the same
    tokens for every request with the live-KV attention kernel and with
    ``decode_attention`` in its place."""
    from repro.models import transformer
    api = build_model(get_config("stablelm-1.6b").reduced())
    params = api.init(jax.random.PRNGKey(0))

    def trace():
        return synthetic_trace(api.cfg, num_requests=5, seed=3,
                               prompt_lens=(5, 11), gen_lens=(3, 9),
                               arrival_every=1)

    eng = ServeEngine(api, params, config=_kernel_config())
    outs = eng.run(trace())
    assert eng.stats["kv_blocks_read"] > 0
    monkeypatch.setattr(transformer, "runs_live_kv", lambda *a, **k: False)
    plain = ServeEngine(api, params, config=_kernel_config())
    want = plain.run(trace())
    # decode_attention reads the whole arena: 3 slots x one 256-block
    assert plain.stats["kv_blocks_read"] == plain.stats["kv_blocks_arena"] \
        == plain.stats["decode_steps"] * 3 > 0
    assert {r: o.tokens for r, o in outs.items()} == \
        {r: o.tokens for r, o in want.items()}


def test_engine_counts_kv_blocks_by_hand(tmp_path):
    """``kv_blocks_read`` / ``kv_blocks_arena`` on a fixed trace: 256-token
    blocks of a 512-token arena; request 0 decodes at lengths 255..258
    (1, 1, 2, 2 blocks), request 1 at 11 and 12 (1, 1).  The emit spans
    carry the same counts."""
    api = build_model(get_config("stablelm-1.6b").reduced())
    eng = ServeEngine(api, api.init(jax.random.PRNGKey(0)),
                      config=_kernel_config(num_slots=2, cache_len=512))
    reqs = [Request(0, np.full((254,), 3, np.int32), 5, arrival=0),
            Request(1, np.full((10,), 4, np.int32), 3, arrival=0)]
    with jax.profiler.trace(str(tmp_path)):
        _ticks(eng, reqs)
    st = eng.stats
    assert st["kv_blocks_read"] == (1 + 1 + 2 + 2) + (1 + 1)
    assert st["kv_blocks_arena"] == st["decode_steps"] * 2 * 2
    emits = [a for n, _, _, a in _engine_spans(str(tmp_path))
             if n == "engine.emit"]
    assert emits
    for key in ("kv_blocks_read", "kv_blocks_arena"):
        assert sum(a[key] for a in emits) == st[key]


def _decode_jaxprs(path):
    """Jaxprs of one decode step on ``path`` with a live mask and without
    one (same arguments)."""
    from jax.sharding import Mesh
    from repro.runtime.engine import _promote_arena
    from repro.runtime.paging import build_spec, paged_tree
    arch = "mixtral-8x7b" if path == "window" else "stablelm-1.6b"
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    B, S = 2, 200 if path == "part_block" else 256
    scope = dict(use_kernels=True, interpret=True)
    if path == "window":         # a 256-position cache past the 32 window
        cache = dataclasses.replace(cfg, window=None)
        cache = _promote_arena(build_model(cache).init_cache(B, S), B)
    else:
        cache = _promote_arena(api.init_cache(B, S), B)
    if path == "paged":
        spec, _ = build_spec(api, B, S, 8, None, "fp32")
        cache = paged_tree(cache, B, spec)
    if path == "kernels_off":
        scope = dict(use_kernels=False)
    if path == "mesh":
        scope["spmd_mesh"] = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                                  ("data", "model"))
    params = api.init(jax.random.PRNGKey(0))
    args = (params, cache, jnp.ones((B, 1), jnp.int32),
            jnp.asarray([True, False]))
    with sparse_execution(**scope):
        with_live = jax.make_jaxpr(
            lambda p, c, t, l: api.decode_step(p, c, t, live=l))(*args)
        without = jax.make_jaxpr(
            lambda p, c, t, l: api.decode_step(p, c, t))(*args)
    return str(with_live), str(without)


@pytest.mark.parametrize("path", ["kernels_off", "mesh", "paged", "window",
                                  "part_block", "fixed_arena"])
def test_decode_paths_keep_plain_attention(path):
    """Only the single-device kernel path over a fixed arena of whole
    256-position KV blocks runs the live-KV kernel (and reads ``live``);
    the paged arena, a mesh scope, a window narrower than the cache, an
    arena whose length is not a block multiple (the serving CLI's derived
    lengths) and kernels off keep ``decode_attention`` and ignore the
    mask."""
    with_live, without = _decode_jaxprs(path)
    kernel = "name=decode_attention"
    if path == "fixed_arena":
        assert kernel in with_live and with_live != without
    else:
        assert kernel not in with_live
        assert with_live == without
