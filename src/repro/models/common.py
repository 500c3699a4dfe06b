"""Shared model components: norms, rope, initializers, tree utilities, and
``griffin_linear`` — the per-GEMM entry point of the sparse execution
substrate (DESIGN.md Section 4)."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.platform import kernel_interpret
from ..core.hybrid import select_mode
from ..core.spec import Mode
from ..kernels.dense_gemm import ops as _dense_ops
from ..kernels.dense_gemm.ops import dense_matmul
from ..kernels.griffin_spmm import ops as _spmm_ops
from ..kernels.griffin_spmm.ops import GriffinWeights, griffin_matmul
from ..kernels.sparse_a import ops as _sparse_a_ops
from ..kernels.sparse_a.ops import sparse_a_matmul

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# sparse execution substrate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseExecution:
    """Static (trace-time) knobs for ``griffin_linear``.

    ``use_kernels`` routes even dense GEMMs through the Pallas substrate
    (off by default: plain ``x @ w`` keeps training/serving behaviour
    byte-identical until a caller opts in).  ``a_sparsity`` is the
    *declared* activation sparsity of the workload category (paper
    Table I) — it must be a concrete float because the mode decision picks
    between kernels at trace time (DESIGN.md Section 5).

    ``spmd_mesh`` (a ``jax.sharding.Mesh`` with > 1 device) switches every
    GEMM to the mesh-partitionable path (DESIGN.md Section 10): inputs and
    outputs are pinned replicated with sharding constraints so GSPMD never
    splits a contraction dim, and each kernel call is wrapped in
    ``shard_map`` — ``pallas_call`` has no GSPMD partitioning rule, but
    the output-axis-only layout makes every device's GEMM fully local, so
    the *real* kernels run per shard (``griffin_matmul(mesh=...)``,
    ``sparse_a_matmul(mesh=...)``, ``dense_matmul(mesh=...)``) with zero
    in-kernel collectives.  ``spmd_kernels=False`` retires that path and
    forces the decompaction/dense-product oracles
    (``griffin_matmul(spmd=True)``, ``sparse_a_matmul(spmd=True)``) —
    kept as the parity reference, no longer the hot loop.  A 1-device
    mesh (or None) keeps the single-device kernel paths byte-identical to
    before.
    """

    use_kernels: bool = False
    interpret: bool = False
    a_sparsity: float = 0.0
    block_m: int = 128
    spmd_mesh: Optional[Any] = None
    spmd_kernels: bool = True


_EXEC_STACK = [SparseExecution()]


@contextlib.contextmanager
def sparse_execution(use_kernels: bool = True, interpret: bool = False,
                     a_sparsity: float = 0.0, block_m: int = 128,
                     spmd_mesh: Optional[Any] = None,
                     spmd_kernels: bool = True):
    """Scope under which ``griffin_linear`` dispatches to the Pallas
    kernels (mode per GEMM via ``core.hybrid.select_mode``).

    The scope is consulted at **trace time** and is not part of any jit
    cache key: a function jitted (traced) outside the scope keeps its
    dense trace when later called inside one, and vice versa.  Enter the
    scope before the first call of a jitted function — or jit inside the
    scope — exactly as with any trace-time constant (DESIGN.md Section 5).
    """
    _EXEC_STACK.append(SparseExecution(use_kernels=use_kernels,
                                       interpret=interpret,
                                       a_sparsity=a_sparsity,
                                       block_m=block_m,
                                       spmd_mesh=spmd_mesh,
                                       spmd_kernels=spmd_kernels))
    try:
        yield _EXEC_STACK[-1]
    finally:
        _EXEC_STACK.pop()


# Trace-time dispatch telemetry: ``griffin_linear`` bumps one bucket per
# GEMM it *traces* (jitted callers never re-enter at run time), so an
# engine test can assert the real-kernel shard_map path — not the oracle —
# was taken, turning a silent fallback regression into a test failure
# (DESIGN.md Section 10).  Buckets:
#   "kernel"      single-device Pallas kernel paths
#   "shard_map"   shard_map'd Pallas kernels under an spmd_mesh scope
#   "spmd_oracle" the decompaction / dense-product SPMD oracles
#   "plain"       plain jnp dots (no kernel requested)
KERNEL_DISPATCH: Dict[str, int] = {}


def reset_kernel_dispatch() -> None:
    KERNEL_DISPATCH.clear()


def kernel_dispatch_counts() -> Dict[str, int]:
    return dict(KERNEL_DISPATCH)


def _dispatched(bucket: str) -> None:
    KERNEL_DISPATCH[bucket] = KERNEL_DISPATCH.get(bucket, 0) + 1


def execution_context() -> SparseExecution:
    return _EXEC_STACK[-1]


def _replicated(x: jax.Array, mesh) -> jax.Array:
    """Pin ``x`` fully replicated on ``mesh`` (an all-gather when it
    arrived sharded).  The mesh-serving GEMM contract (DESIGN.md
    Section 10): replicated activations x output-axis-sharded weights mean
    every contraction runs whole on every device, so GSPMD collectives
    only ever *move* values — nothing reorders a floating-point reduction
    and the sharded trace stays bit-identical to the single-device one."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec()))


@jax.named_scope("griffin_linear")
def griffin_linear(x: jax.Array, w) -> jax.Array:
    """The weight GEMM of the model stack: ``x @ w`` morphed per call.

    ``w`` is either a plain array (dense weights) or a ``GriffinWeights``
    (block-compacted, produced by ``repro.sparsity.sparsify_params``).  The
    execution mode follows ``core.hybrid.select_mode`` over the declared
    activation sparsity and the weight representation:

      dense w, dense a  -> plain ``x @ w`` (or the dense Pallas kernel
                           when the ``sparse_execution`` scope is active)
      dense w, sparse a -> Sparse.A kernel (runtime-compacted A)
      GriffinWeights    -> Sparse.B kernel; dual when a is also declared
                           sparse (on-the-fly A-block predication)

    Under a multi-device ``spmd_mesh`` scope the same dispatch wraps each
    kernel call in ``shard_map`` with replicated inputs and outputs
    (``_replicated``; DESIGN.md Section 10): the output-axis-only layout
    makes every device's GEMM fully local, so the real kernels run per
    shard and the replication constraints keep every reduction whole —
    sharding never changes a logit bit.  Weights whose output axis does
    not split evenly over the model axis — or any GEMM when the scope
    sets ``spmd_kernels=False`` — take the decompaction / dense-product
    oracle instead (interpret mode is forced on platforms that need it,
    ``configs.platform.kernel_interpret``, since mesh jit sets are traced
    after placement).

    Leading batch/sequence axes are flattened into the GEMM M axis.  The
    whole call runs under the name scope ``griffin_linear``, so the ops it
    lowers to carry it in their op-name metadata.
    """
    ctx = _EXEC_STACK[-1]
    mesh = ctx.spmd_mesh
    spmd = mesh is not None and mesh.size > 1
    mp = (mesh.shape.get("model", 0)
          if spmd and "model" in mesh.axis_names else 0)
    if spmd:
        x = _replicated(x, mesh)
    if isinstance(w, GriffinWeights):
        lead = x.shape[:-1]
        mode = select_mode(ctx.a_sparsity, 1.0)
        x2 = x.reshape(-1, x.shape[-1])
        dual = mode == Mode.AB
        if spmd and ctx.spmd_kernels and mp and _spmm_ops.shardable(w, mp):
            _dispatched("shard_map")
            out = griffin_matmul(x2, w, block_m=ctx.block_m, dual=dual,
                                 interpret=ctx.interpret or kernel_interpret(),
                                 mesh=mesh)
        elif spmd:
            _dispatched("spmd_oracle")
            out = griffin_matmul(x2, w, block_m=ctx.block_m, dual=dual,
                                 spmd=True)
        else:
            _dispatched("kernel")
            out = griffin_matmul(x2, w, block_m=ctx.block_m, dual=dual,
                                 interpret=ctx.interpret)
        out = out.reshape(*lead, w.n).astype(x.dtype)
        return _replicated(out, mesh) if spmd else out
    if not ctx.use_kernels and not spmd:
        return x @ w
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    sparse_a = select_mode(ctx.a_sparsity, 0.0) == Mode.A
    if spmd:
        kern_ops = _sparse_a_ops if sparse_a else _dense_ops
        if (ctx.use_kernels and ctx.spmd_kernels and mp
                and kern_ops.shardable(w, mp)):
            _dispatched("shard_map")
            interp = ctx.interpret or kernel_interpret()
            out = (sparse_a_matmul(x2, w, block_m=ctx.block_m,
                                   interpret=interp, mesh=mesh)
                   if sparse_a else
                   dense_matmul(x2, w, block_m=ctx.block_m,
                                interpret=interp, mesh=mesh))
        elif ctx.use_kernels and sparse_a:
            _dispatched("spmd_oracle")
            out = sparse_a_matmul(x2, w, spmd=True)
        else:
            _dispatched("spmd_oracle" if ctx.use_kernels else "plain")
            out = x2 @ w
    elif sparse_a:
        _dispatched("kernel")
        out = sparse_a_matmul(x2, w, block_m=ctx.block_m,
                              interpret=ctx.interpret)
    else:
        _dispatched("kernel")
        out = dense_matmul(x2, w, block_m=ctx.block_m,
                           interpret=ctx.interpret)
    out = out.reshape(*lead, w.shape[-1]).astype(x.dtype)
    return _replicated(out, mesh) if spmd else out


def write_kv_slot(cache: jax.Array, update: jax.Array, slot: jax.Array
                  ) -> jax.Array:
    """Write a one-token K/V update into a (B, S, ...) cache at ``slot``.

    ``slot`` is a scalar (lockstep batch: one shared sequence index) or a
    (B,) vector of per-row indices (continuous-batching slot pools,
    runtime/engine.py) — the vector path is a per-row
    ``dynamic_update_slice`` under ``vmap`` and is bit-identical to the
    scalar path when all entries are equal.  ``update``: (B, 1, ...).
    """
    if slot.ndim:
        upd = jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))
        return upd(cache, update, slot)
    return jax.lax.dynamic_update_slice(cache, update, (0, slot, 0, 0))


def write_kv_layer(cache: jax.Array, layer: jax.Array, update: jax.Array,
                   slot: jax.Array) -> jax.Array:
    """``write_kv_slot`` into layer ``layer`` of a layer-stacked
    (L, B, S, ...) cache.  The decode layer loop carries the whole stacked
    cache and writes each layer's one-token update into it in place: a
    loop that instead re-emits every layer's slice as a fresh output holds
    several copies of the cache at once, which at 8 slots x 2048 tokens of
    a 24-layer model no longer fits one 16 GB chip."""
    if slot.ndim:
        # one scatter over (layer, row, slot): a vmap over the batch axis
        # would move it to the front and transpose the whole cache
        rows = jnp.arange(update.shape[0])
        return cache.at[layer, rows, slot].set(update[:, 0])
    return jax.lax.dynamic_update_slice(cache, update[None],
                                        (layer, 0, slot, 0, 0))


def paged_write(pool: jax.Array, scale: Optional[jax.Array],
                pages: jax.Array, update: jax.Array, pos: jax.Array,
                page_size: int, layer: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Write a one-token K/V update into a paged pool (DESIGN.md Sec. 14).

    ``pool``: (num_pages, page_size, ...) shared physical pages;
    ``pages``: (B, max_pages) int32 page table (logical page j of row b ->
    physical page id); ``update``: (B, 1, ...); ``pos``: scalar or (B,)
    per-row position, exactly as ``write_kv_slot`` takes it.  Positions
    wrap at ``max_pages * page_size`` so dead slots (whose positions keep
    advancing after release) stay in range — their table rows point at the
    DUMP page (id 0), which is never read, so their garbage writes are
    discarded by construction.  When ``scale`` is given the pool is int8:
    the row is quantized on the way in (optim.compression.quantize_rows)
    and its per-token scale stored alongside.  ``layer`` addresses one
    layer of layer-stacked (L, num_pages, ...) pools, written in place
    (``write_kv_layer``).
    """
    from ..optim.compression import quantize_rows
    B, maxp = pages.shape
    posv = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)
    slot = posv % (maxp * page_size)
    pid = jnp.take_along_axis(pages, (slot // page_size)[:, None],
                              axis=1)[:, 0]
    off = slot % page_size
    idx = (pid, off) if layer is None else (layer, pid, off)
    row = update[:, 0]
    if scale is not None:
        q, s = quantize_rows(row, 1)
        return pool.at[idx].set(q), scale.at[idx].set(s)
    return pool.at[idx].set(row.astype(pool.dtype)), None


def paged_view(pool: jax.Array, scale: Optional[jax.Array],
               pages: jax.Array, dtype: Any,
               layer: Optional[jax.Array] = None) -> jax.Array:
    """Gather each row's pages into a (B, max_pages * page_size, ...) view.

    The engine rounds ``cache_len`` up to ``max_pages * page_size``, so
    this view has exactly the fixed arena's (B, cache_len, ...) shape —
    ``decode_attention``'s position mask then sees identical shapes and
    fp32 paged decode is bit-identical to the fixed arena (masked entries
    contribute an exact 0.0 either way).  int8 pools dequantize through
    the per-token scales on the way out.  ``layer`` gathers from one layer
    of layer-stacked pools.
    """
    idx = pages if layer is None else (layer, pages)
    v = pool[idx]                        # (B, max_pages, page_size, ...)
    if scale is not None:
        s = scale[idx]
        v = v.astype(jnp.float32) * s[(...,) + (None,) * (v.ndim - 3)]
    B, maxp, ps = v.shape[:3]
    return v.reshape(B, maxp * ps, *v.shape[3:]).astype(dtype)


def length_mask(lengths: jax.Array, seq_len: int) -> jax.Array:
    """(B,) true prompt lengths -> (B, S) bool validity mask for a
    right-padded token batch (position i valid iff i < length).  The
    bucketed-prefill path (runtime/engine.py, DESIGN.md Section 9) pads
    prompts up to a power-of-two bucket; this mask is what each family's
    prefill threads into its state updates so pad positions are identity."""
    return jnp.arange(seq_len)[None, :] < lengths[:, None]


def take_last(x: jax.Array, lengths: jax.Array) -> jax.Array:
    """Per-row last *valid* timestep of a right-padded (B, S, D) tensor:
    row b -> x[b, lengths[b] - 1].  The bucketed replacement for
    ``x[:, -1]`` (which would read a pad position)."""
    idx = (lengths - 1).astype(jnp.int32)[:, None, None]
    idx = jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[-1]))
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm1p(x: jax.Array, scale: jax.Array, bias: jax.Array,
                 eps: float = 1e-5) -> jax.Array:
    """LayerNorm scaled by ``1 + scale`` plus ``bias`` (Nemotron's
    ``LayerNorm1P``), computed in float32 like ``rms_norm``."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))
            + bias.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         frac: float = 1.0) -> jax.Array:
    """Rotary embedding.  x: (..., seq, heads, head_dim), positions: (seq,)
    or broadcastable to (..., seq).  Only the first ``frac * head_dim``
    channels of each head rotate, with frequencies over that width (HF's
    ``partial_rotary_factor``); the rest pass through unchanged."""
    hd = x.shape[-1]
    rot = int(hd * frac)
    half = rot // 2
    freqs = (1.0 / theta) ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    if rot < hd:
        out = jnp.concatenate([out, x[..., rot:]], axis=-1)
    return out


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) *
            scale).astype(dtype)


def stack_layers(init_one: Callable[[jax.Array], Params], key: jax.Array,
                 n: int) -> Params:
    """Initialize n layers and stack each leaf along a leading axis, the
    layout ``lax.scan`` consumes.  n == 0 yields empty-stacked leaves (scan
    over length-0 xs is a no-op), so irregular depth patterns degrade
    gracefully in reduced configs.

    The layers are one ``vmap`` over their keys — the same values as
    initializing them one by one and stacking, but one layer's worth of
    program: a jitted init (the sharded four-chip path) otherwise traces
    and compiles every layer separately."""
    if n == 0:
        proto = jax.eval_shape(init_one, key)
        return jax.tree.map(
            lambda x: jnp.zeros((0,) + x.shape, x.dtype), proto)
    return jax.vmap(init_one)(jax.random.split(key, n))


def layer_scan(use_scan: bool, body: Callable, carry, xs):
    """``lax.scan`` over stacked layers, or an unrolled python loop.

    The unrolled form exists for the roofline cost pass: XLA's
    HloCostAnalysis counts a while-loop body once regardless of trip count,
    so per-layer costs are extracted from *unrolled* lowers of 1 vs 2 layers
    (launch/dryrun.py) while production compiles use the scan (compile time
    independent of depth).
    """
    n = jax.tree.leaves(xs)[0].shape[0]
    if use_scan or n == 0:
        # length-0 stacks produce structurally-correct empty ys via scan
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs, 0), *ys)
    else:
        ys = None
    return carry, ys


def remat_fn(cfg, body: Callable) -> Callable:
    """Apply the configured rematerialization policy to a layer body."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def act_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
            "relu2": lambda x: jnp.square(jax.nn.relu(x)),
            }[name]


def param_count(params: Params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree.leaves(params)))


def cast_tree(params: Params, dtype) -> Params:
    return jax.tree.map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating)
        else p, params)
