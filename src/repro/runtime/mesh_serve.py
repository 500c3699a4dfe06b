"""Mesh-parallel serving: the fused-chunk engine partitioned over a
("data", "model") device mesh (DESIGN.md Section 10).

``MeshServeEngine`` is the multi-device face of ``runtime.engine
.ServeEngine``: same scheduler, same host mirror, same fused decode-chunk
ladder — but parameters live model-sharded (output-axis-only TP via
``runtime.sharding.shard_params(serve=True)``, with ``GriffinWeights``
b_comp sharding its N axis and the kidx/cnt/inv_perm scalar-prefetch
metadata replicated), and the slot-pool KV arena shards its batch (slot)
axis over "data" and its head axes over "model"
(``runtime.sharding.shard_cache(decode=True)``).  Every per-Mode jit set
(prefill and the fused chunk scan) is traced with explicit
``in_shardings``/``out_shardings`` plus donation, so the arena updates in
place *sharded* and only the (chunk, B) token ring, the admissions' first
tokens, and the live-rows zero-fraction scalars cross back to the host —
the host-sync budget of DESIGN.md Section 9 is unchanged by sharding.

The layout is chosen so that no floating-point reduction is ever split
across devices (contraction dims and softmax axes stay whole; sharded
axes are output/batch/head axes, all reduction-free), which makes the
sharded engine's logits — and therefore its greedy tokens — bit-identical
to the single-device engine on the same trace, for all four execution
Modes.  Because no GEMM's contraction dim is ever split, each device's
share of every matmul is fully local, and ``models.common.griffin_linear``
runs the *real* Pallas kernels on every mesh size by wrapping them in
``jax.shard_map`` with zero in-kernel collectives — each
device executes ``griffin_matmul_shard``/``sparse_a_matmul_shard``/
``dense_matmul_shard`` on its N-slice (DESIGN.md Section 10).  The former
jnp fallbacks (``griffin_matmul(spmd=True)`` decompaction, plain sharded
dots) are retired from the hot loop and kept only as the parity oracle,
reachable via ``spmd_kernels=False``.  ``mesh=1x1`` degenerates to the
single-device engine: the sharding specs are trivial and the kernels run
un-shard_map'd.

Runs unmodified on an emulated CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — which is how
the CI ``sharded`` job executes the parity matrix in
``tests/test_mesh_serve.py`` — and on a real TPU slice via
``launch/serve.py --mesh DxM``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.registry import ModelApi
from .config import resolve_engine_config
from .elastic import plan_mesh, reshard, surviving
from .engine import (EngineSnapshot, ServeEngine, _batch_axes, _make_insert,
                     _make_paged_insert, _promote_arena)
from .paging import PagedSpec, build_spec, paged_tree
from .serve import make_chunk_ladder
from .sharding import shard_cache, shard_params


def cache_heads(api: ModelApi) -> int:
    """Head-axis extent of the model's cache leaves — the size
    ``cache_spec(decode=True)`` matches to place "model" (KV heads for
    attention caches, the head axis of mLSTM/sLSTM states).  Families
    whose cache head count differs from ``num_kv_heads`` simply match
    nothing and keep those leaves replicated (spec-respecting, never
    wrong)."""
    cfg = api.cfg
    return int(getattr(cfg, "num_kv_heads", 0)
               or getattr(cfg, "num_heads", 0) or 0)


def _promoted_arena_shapes(api: ModelApi, num_slots: int,
                           cache_len: int) -> Any:
    """ShapeDtypeStructs of the engine's arena — ``engine._promote_arena``
    over ``init_cache``, exactly what ``_init_device_state`` allocates."""
    return jax.eval_shape(
        lambda: _promote_arena(api.init_cache(num_slots, cache_len),
                               num_slots))


def serve_shardings(api: ModelApi, mesh: Mesh, params: Any, num_slots: int,
                    cache_len: int, *, paged: Optional[PagedSpec] = None
                    ) -> Tuple[Any, Any, NamedSharding]:
    """(param, arena, replicated) NamedSharding trees for the mesh-serving
    layout (DESIGN.md Section 10).  ``params`` is the tree actually being
    served, so block-compacted ``GriffinWeights`` leaves get their own
    b_comp/metadata specs.  ``paged``: the arena's ``PagedSpec`` when the
    engine pages its KV cache (runtime/paging.py) — the arena template is
    then the pool + page-table tree and the paged leaf names route through
    ``cache_spec``'s paged rules (pages replicated, pools dp-sharded on
    their page axis)."""
    p_sh = shard_params(params, mesh, fsdp=False, serve=True)
    arena = _promoted_arena_shapes(api, num_slots, cache_len)
    pset = frozenset()
    if paged is not None:
        arena = paged_tree(arena, num_slots, paged)
        pset = frozenset(paged.paged_keys)
    c_sh = shard_cache(arena, mesh, num_slots, decode=True,
                       heads=cache_heads(api), paged=pset)
    return p_sh, c_sh, NamedSharding(mesh, P())


def init_params_sharded(api: ModelApi, mesh: Mesh, key: jax.Array) -> Any:
    """``api.init`` jitted straight into the serving layout
    (``serve_shardings``' parameter tree): every device generates only its
    own shard, so a model larger than one chip is never whole on any
    device — the four-chip path's precondition."""
    p_sh = shard_params(jax.eval_shape(api.init, key), mesh, fsdp=False,
                        serve=True)
    return jax.jit(api.init, out_shardings=p_sh)(key)


def mesh_serve_fns(api: ModelApi, mesh: Mesh, params: Any, num_slots: int,
                   cache_len: int, decode_chunk: int = 8, shardings=None,
                   paged: Optional[PagedSpec] = None):
    """Returns (prefill_fn, chunk_for, (p_sh, c_sh, rep)) — the
    sharded twin of ``runtime.serve.jit_serve_fns``, shaped for
    ``ServeEngine``'s fns factory (one invocation per selected Mode, each
    traced under that Mode's ``sparse_execution`` scope at first call).

    Batch-1 admission prefills produce a *replicated* cache and logits
    (their batch axis cannot shard), which the sharded ``_insert`` then
    reshards into the arena; the fused chunk scan carries the arena with
    its shardings end to end and donates cache/token/remaining buffers so
    the pool updates in place.  Out-shardings pin the token ring and the
    measurement scalars replicated — they are the only values the host
    fetches per chunk.

    ``shardings``: a precomputed ``serve_shardings`` triple —
    ``MeshServeEngine`` passes its own so the per-Mode factory invocations
    skip four redundant full-tree spec walks.
    """
    p_sh, c_sh, rep = shardings or serve_shardings(api, mesh, params,
                                                   num_slots, cache_len,
                                                   paged=paged)

    def prefill_fn(params, inp):
        return api.prefill(params, inp, cache_len=cache_len)

    prefill_jit = jax.jit(prefill_fn, in_shardings=(p_sh, rep),
                          out_shardings=(rep, rep))
    chunk_for = make_chunk_ladder(
        api, decode_chunk,
        lambda fn: jax.jit(fn,
                           in_shardings=(p_sh, c_sh, rep, rep),
                           out_shardings=(c_sh, rep, rep, rep, rep, rep,
                                          rep),
                           donate_argnums=(1, 2, 3)))
    return prefill_jit, chunk_for, (p_sh, c_sh, rep)


class MeshServeEngine(ServeEngine):
    """``ServeEngine`` partitioned over a ("data", "model") mesh.

    Construction places the (possibly ``GriffinWeights``-compacted) param
    tree onto the serving layout and the slot-pool arena onto the decode
    cache layout; the admission insert is re-jitted with the arena
    shardings (donated, so sharded admissions still update in place); and
    every ``sparse_execution`` scope the engine enters carries
    ``spmd_mesh`` so ``griffin_linear`` shard_maps the real Pallas kernels
    over the model axis (``spmd_kernels=False`` retires them to the
    decompaction oracle).  All host-side bookkeeping — scheduler,
    remaining mirror, ring
    drain, measurement cadence, Mode-keyed jit sets — is inherited
    untouched, which is the point: sharding is a placement concern, not a
    scheduling one (DESIGN.md Section 10).

    ``mesh=1x1`` (``launch.mesh.serve_mesh("1x1")``) is the single-device
    special case: specs are trivial, ``spmd_mesh`` stays None, and the
    engine behaves exactly like ``ServeEngine`` with sharding-annotated
    jits.

    Failure handling (DESIGN.md Section 11): on a detected ``DeviceLoss``
    (or a straggler eviction — hosts are the data-rows of the mesh), the
    inherited recovery rolls back to the tick-start snapshot and this class
    rebuilds the whole device story on the survivors — ``elastic.plan_mesh``
    plans the new mesh (TP degree capped by ``recovery_model_parallel``,
    default the current model-axis size), ``serve_shardings`` re-derives the
    layout, the Mode-keyed jit sets are dropped (they bake the old mesh's
    in/out-shardings), and params/arena/counters reshard via
    ``elastic.reshard`` (or ``checkpoint.restore`` when snapshots go to
    disk).  Because every mesh serves bit-identical tokens (Section 10),
    the finished trace equals an uninterrupted run's token for token.
    """

    def __init__(self, api: ModelApi, params: Any, *, mesh: Mesh,
                 config=None, fns_factory: Optional[Callable] = None,
                 fault_injector=None, straggler=None, **legacy):
        missing = {"data", "model"} - set(mesh.axis_names)
        if missing:
            raise ValueError(f"serving mesh needs axes ('data', 'model'), "
                             f"got {mesh.axis_names}")
        # resolve the config here (legacy kwargs fold in and warn once) so
        # the sharding layout can be derived before the base constructor
        # allocates anything; the base re-resolution is then a no-op.
        config = resolve_engine_config(config, legacy, type(self).__name__)
        if config.arena.cache_len is None:
            raise ValueError("MeshServeEngine needs arena.cache_len")
        num_slots = config.arena.num_slots
        paged, cache_len = build_spec(
            api, num_slots, config.arena.cache_len, config.arena.page_size,
            config.arena.num_pages, config.arena.kv_dtype)
        if cache_len != config.arena.cache_len:
            config = config.with_fields(cache_len=cache_len)
        self.mesh = mesh
        self._recovery_mp = config.fault.recovery_model_parallel
        if mesh.size > 1:
            self._spmd_mesh = mesh          # class default is None
        self._shardings = serve_shardings(api, mesh, params, num_slots,
                                          cache_len, paged=paged)
        params = jax.tree.map(jax.device_put, params, self._shardings[0])
        if fns_factory is None:
            # late-bound self.mesh/self._shardings: after a recovery remesh
            # the per-Mode factory invocations trace for the new layout
            fns_factory = lambda: mesh_serve_fns(
                api, self.mesh, self.params, num_slots, cache_len,
                decode_chunk=self.decode_chunk, shardings=self._shardings)
        super().__init__(api, params, config=config, fns_factory=fns_factory,
                         fault_injector=fault_injector, straggler=straggler)

    def _init_device_state(self) -> None:
        """Sharded twin of the base allocation: arena placed on the decode
        cache layout, ``_insert`` jitted with the arena in/out shardings
        (pool donated), token/remaining buffers replicated — they return
        to the host every chunk anyway."""
        cache = self._arena()
        _, c_sh, rep = self._shardings
        self.cache = jax.tree.map(jax.device_put, cache, c_sh)
        self._build_insert()
        self._tokens = jax.device_put(
            jnp.zeros((self.num_slots, 1), jnp.int32), rep)
        self._remaining = jax.device_put(
            jnp.zeros((self.num_slots,), jnp.int32), rep)

    def _build_insert(self) -> None:
        """Admission insert carrying the *current* arena shardings —
        rebuilt by recovery after every remesh.  The paged variant takes
        the extra replicated page-row operand (runtime/paging.py)."""
        _, c_sh, rep = self._shardings
        axes = _batch_axes(self.api, self.cache_len)
        if self._paged is not None:
            wrap = lambda f: jax.jit(
                f, in_shardings=(c_sh, rep, rep, rep, rep, rep, rep, rep),
                out_shardings=(c_sh, rep, rep, rep),
                donate_argnums=(0, 1, 2))
            self._insert = _make_paged_insert(axes, self._paged,
                                              jit_wrap=wrap)
        else:
            wrap = lambda f: jax.jit(
                f, in_shardings=(c_sh, rep, rep, rep, rep, rep, rep),
                out_shardings=(c_sh, rep, rep, rep),
                donate_argnums=(0, 1, 2))
            self._insert = _make_insert(axes, jit_wrap=wrap)

    # -- failure handling (DESIGN.md Section 11) ----------------------------

    def _mesh_desc(self) -> str:
        from ..launch.mesh import mesh_spec
        return mesh_spec(self.mesh)

    def _host_device_ids(self, host: int) -> list:
        """Hosts are the data-rows of the serving mesh's device array; a
        row index beyond the (possibly already shrunk) mesh owns nothing."""
        rows = self.mesh.devices
        if host >= rows.shape[0]:
            return []
        return [int(d.id) for d in rows[host].flat]

    def _survivors_exist(self, lost) -> bool:
        return bool(surviving(self.mesh.devices, lost))

    def _remesh(self, lost) -> None:
        """``elastic.plan_mesh`` over the survivors, then rebuild everything
        that baked the old mesh: sharding specs, the model-sharded params
        (from the host-side copy — the dead devices' shards are gone), the
        Mode-keyed jit sets, and the admission insert."""
        survivors = surviving(self.mesh.devices, lost)
        if not survivors:
            raise RuntimeError(f"no surviving devices after losing {lost}")
        mp = self._recovery_mp or int(self.mesh.shape["model"])
        self.mesh = plan_mesh(len(survivors), mp, devices=survivors)
        self._spmd_mesh = self.mesh if self.mesh.size > 1 else None
        self._shardings = serve_shardings(self.api, self.mesh,
                                          self._params_host, self.num_slots,
                                          self.cache_len, paged=self._paged)
        self.params = reshard(self._params_host, self._shardings[0])
        self._mode_fns.clear()      # jits bake in/out-shardings: retrace
        self._build_insert()

    def _restore_device(self, snap: EngineSnapshot) -> None:
        """Place the snapshot's arena/counters onto the (new) mesh's decode
        layout — through ``checkpoint.restore`` when the snapshot went to
        disk (which also re-reads the compacted params), else
        ``elastic.reshard`` from the in-memory copy."""
        p_sh, c_sh, rep = self._shardings
        shardings = {"cache": c_sh, "tokens": rep, "remaining": rep}
        if snap.ckpt_step is not None:
            shardings["params"] = p_sh
            state = self._snapshot_state(snap, shardings=shardings)
            self.params = state["params"]
        else:
            state = {k: reshard(v, shardings[k])
                     for k, v in self._snapshot_state(snap, None).items()}
        self.cache = state["cache"]
        self._tokens = state["tokens"]
        self._remaining = state["remaining"]
