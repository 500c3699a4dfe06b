"""Serving runtime: batched prefill + decode with sharded KV caches, the
fused multi-step decode chunk (DESIGN.md Section 9), and prompt-bucket
padding shared by the engine and its greedy oracle.

``jit_serve_fns`` is the *lockstep* sharded factory (dp logits, pooled
decode); the mesh-parallel slot-pool engine builds its per-Mode jit sets
from ``runtime.mesh_serve.mesh_serve_fns`` instead, which reuses
``make_chunk_ladder``/``make_decode_chunk_fn`` below with the serving
layout's explicit shardings (DESIGN.md Section 10).

Everything here is stateless in the engine's failure-handling sense: these
factories hold no arena or scheduler state, so elastic recovery (DESIGN.md
Section 11) rebuilds them freely on the post-loss mesh — only the jit
caches are lost, never tokens."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.registry import ModelApi
from .sharding import shard_batch, shard_cache, shard_params


def pad_prompt_batch(batch: Dict[str, jax.Array],
                     bucket: Optional[int]) -> Dict[str, jax.Array]:
    """Right-pad ``batch["tokens"]`` to ``bucket`` and record the true
    prompt lengths under ``"lengths"`` — the input contract of every
    family's bucketed prefill (DESIGN.md Section 9).  ``bucket=None`` is
    the identity (exact-length prefill, no lengths threaded), so callers
    can pass ``ServeEngine.bucket_for(...)`` verbatim."""
    if bucket is None:
        return batch
    toks = batch["tokens"]
    B, S = toks.shape
    if bucket < S:
        raise ValueError(f"bucket {bucket} shorter than prompt {S}")
    out = dict(batch)
    out["tokens"] = jnp.pad(toks, ((0, 0), (0, bucket - S)))
    out["lengths"] = jnp.full((B,), S, jnp.int32)
    return out


def make_chunk_ladder(api: ModelApi, decode_chunk: int,
                      jit_wrap: Callable[[Callable], Callable]) -> Callable:
    """Build ``chunk_for(n)``: a memoized fused-chunk executable per scan
    length on the engine's power-of-two ladder 1..``decode_chunk``
    (``ServeEngine._chunk_len``), so at most log2(decode_chunk)+1 traces
    exist per mode.  ``jit_wrap`` supplies the jit policy (plain donation
    for single-host, shardings on a mesh); the cap is validated here so
    both paths enforce the same ladder contract."""
    cache: Dict[int, Callable] = {}

    def chunk_for(n: int) -> Callable:
        if n < 1 or n > decode_chunk:
            raise ValueError(f"chunk length {n} outside the configured "
                             f"ladder 1..{decode_chunk}")
        fn = cache.get(n)
        if fn is None:
            fn = jit_wrap(make_decode_chunk_fn(api, n))
            cache[n] = fn
        return fn

    return chunk_for


def make_decode_chunk_fn(api: ModelApi, decode_chunk: int) -> Callable:
    """Build the fused multi-step decode tick: one ``lax.scan`` over
    ``decode_chunk`` pooled decode steps with argmax, token feedback and
    per-slot bookkeeping all on device (DESIGN.md Section 9).

    Carry: (cache, tokens (B, 1) int32, remaining (B,) int32 — tokens each
    slot still owes, 0 for free/unadmitted slots).  Per step the live mask
    is ``remaining > 0``; live rows contribute their exact-zero logit
    fraction to a running (num, den) pair — the engine's workload-category
    measurement — and decrement ``remaining``.  Returns the small arrays
    the host actually needs: the (chunk, B) token ring, the two
    measurement scalars and the (2,) KV-block counts of the chunk's
    attention (``ModelApi.kv_blocks``: blocks read and blocks held, per
    layer, summed over the steps; zeros where the model gives none).  Finished and never-admitted rows keep decoding
    garbage (row-wise independence makes that harmless — DESIGN.md
    Section 8); they are excluded from both the ring drain (host side) and
    the measurement (the live mask here).  The decode step gets the live
    mask too (``decode_step(..., live=)``), so attention can skip the dead
    rows' KV.
    """

    def chunk_fn(params, cache, tokens, remaining):
        def body(carry, _):
            cache, tokens, remaining = carry
            live = remaining > 0
            kv = (jnp.zeros((2,), jnp.int32) if api.kv_blocks is None
                  else api.kv_blocks(cache, live))
            logits, cache = api.decode_step(params, cache, tokens, live=live)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)       # (B,)
            zf_rows = jnp.mean((logits == 0).astype(jnp.float32), axis=-1)
            zf_num = jnp.sum(zf_rows * live)
            zf_den = jnp.sum(live.astype(jnp.float32))
            remaining = remaining - live.astype(remaining.dtype)
            return ((cache, toks[:, None], remaining),
                    (toks, zf_num, zf_den, kv))

        carry, (ring, nums, dens, kvs) = jax.lax.scan(
            body, (cache, tokens, remaining), length=decode_chunk)
        cache, tokens, remaining = carry
        return (cache, tokens, remaining, ring, nums.sum(), dens.sum(),
                kvs.sum(0))

    return chunk_fn


def jit_serve_fns(api: ModelApi, mesh: Mesh, batch: int, cache_len: int,
                  fsdp: bool = False, params: Optional[Any] = None,
                  decode_chunk: int = 8):
    """Returns (prefill_fn, decode_fn, chunk_for, (p_sh, c_sh, logits_sh)).

    ``params`` is the tree actually being served — pass it whenever it is
    not shaped like ``api.init``'s output (block-compacted ``GriffinWeights``
    leaves from ``sparsity.sparsify_params`` replace single arrays with
    metadata subtrees, each needing its own spec from
    ``runtime.sharding.param_spec``); defaults to the dense init shapes.

    These are the fns the serving engine drives (``runtime.engine
    .ServeEngine`` takes ``lambda: jit_serve_fns(...)`` as its fns
    factory): ``prefill_fn`` admits one request (its output cache is
    slot-inserted into the pool arena), ``decode_fn`` advances the whole
    pool one step, and ``chunk_for(n)`` returns the fused n-step tick the
    engine actually serves with (up to ``decode_chunk`` pooled steps per
    host round-trip; see :func:`make_decode_chunk_fn`) — cache, token and
    remaining buffers all donated so the arena updates in place.
    ``logits_sh`` is the dp-sharded logits layout both fns produce — it
    assumes the pool batch divides the dp axes, so batch-1 admission
    prefills need a 1-dp mesh (multi-host serving buckets prefills on a
    separate dp=1 mesh; see DESIGN.md Section 8).

    Serving defaults to fsdp=False: parameters live model-sharded and
    replicated over the data axis so decode steps pay no per-step parameter
    all-gathers (the train-path FSDP layout would; see EXPERIMENTS.md
    Section Perf).
    """
    p_shapes = (jax.eval_shape(api.init, jax.random.PRNGKey(0))
                if params is None else params)
    p_sh = shard_params(p_shapes, mesh, fsdp=fsdp)
    cache_shapes = jax.eval_shape(lambda: api.init_cache(batch, cache_len))
    c_sh = shard_cache(cache_shapes, mesh, batch)
    rep = NamedSharding(mesh, P())

    def prefill_fn(params, inp):
        return api.prefill(params, inp, cache_len=cache_len)

    def decode_fn(params, cache, token):
        return api.decode_step(params, cache, token)

    logits_sh = NamedSharding(mesh, P(*(("pod", "data") if "pod" in
                                        mesh.axis_names else ("data",)),)
                              ) if batch % _dp(mesh) == 0 else rep
    prefill_jit = jax.jit(prefill_fn,
                          in_shardings=(p_sh, None),
                          out_shardings=(c_sh, logits_sh))
    decode_jit = jax.jit(decode_fn,
                         in_shardings=(p_sh, c_sh, None),
                         out_shardings=(logits_sh, c_sh),
                         donate_argnums=(1,))
    chunk_for = make_chunk_ladder(
        api, decode_chunk,
        lambda fn: jax.jit(fn,
                           in_shardings=(p_sh, c_sh, rep, rep),
                           out_shardings=(c_sh, rep, rep, rep, rep, rep,
                                          rep),
                           donate_argnums=(1, 2, 3)))
    return prefill_jit, decode_jit, chunk_for, (p_sh, c_sh, logits_sh)


def _dp(mesh: Mesh) -> int:
    n = mesh.shape.get("data", 1)
    n *= mesh.shape.get("pod", 1)
    return n


def greedy_generate(api: ModelApi, params, batch: Dict, steps: int,
                    cache_len: int, prompt_bucket: Optional[int] = None,
                    fns: Optional[Tuple[Callable, Callable]] = None):
    """Reference generation loop, one static batch in lockstep — the parity
    oracle for the continuous-batching engine (``runtime.engine``): per-slot
    decode is row-wise independent, so the engine's tokens for a request
    must match a batch-1 greedy run of the same prompt token for token
    (tests/test_engine.py asserts this, dense and sparse).

    ``prompt_bucket`` replays the engine's bucketed-prefill path (pass
    ``engine.bucket_for(prompt_len)``): the prompt is right-padded to the
    bucket with lengths threaded, so the oracle runs the *same padded
    computation* the engine admitted the request with — the definition of
    token parity under bucketing (DESIGN.md Section 9).

    ``fns``: jitted ``(prefill(params, batch), decode_step(params, cache,
    token))`` to run the loop with — e.g. an engine's prefill jit and a
    batch-1 decode jit traced under its Mode scope — so the oracle
    compiles once per shape instead of once per step (an eager
    ``lax.scan`` over the layers retraces and recompiles on every call,
    which at published widths costs seconds per token).  Default: the
    eager model functions."""
    batch = pad_prompt_batch(batch, prompt_bucket)
    if fns is None:
        fns = (lambda p, b: api.prefill(p, b, cache_len=cache_len),
               api.decode_step)
    prefill_fn, decode_fn = fns
    cache, logits = prefill_fn(params, batch)
    toks = [jnp.argmax(logits, -1).astype(jnp.int32)[:, None]]
    for _ in range(steps - 1):
        logits, cache = decode_fn(params, cache, toks[-1])
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32)[:, None])
    return jnp.concatenate(toks, axis=1)
