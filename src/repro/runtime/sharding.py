"""Sharding rules: logical-axis mapping from parameter/cache/batch trees to
``PartitionSpec``s on the production mesh.

Strategy (MaxText-style 2D "FSDP + TP"):
  - weight matrices: penultimate (input) dim -> "data" (FSDP: parameters and
    optimizer states are fully sharded; GSPMD inserts the all-gathers),
    last (output) dim -> "model" (TP) — transposed for output projections so
    matmul contractions stay local;
  - embeddings: vocab -> "model", feature -> "data";
  - activations: batch -> ("pod","data") when divisible, otherwise the
    sequence axis (long-context decode with batch 1);
  - KV caches / recurrent states: batch -> dp axes, head_dim/feature ->
    "model" (kv-heads can be < TP degree, head_dim always divides);
  - the "pod" axis only shards the batch: parameters are replicated across
    pods (FSDP within pod, DP across pods), so cross-pod traffic is gradient
    reduction only;
  - block-compacted weights (GriffinWeights pytrees from
    repro.sparsity.sparsify_params): b_comp shards its output (N) axis by
    the parent GEMM's rule, the compacted K rows stay whole (kidx ids are
    global), scalar-prefetch metadata replicates (DESIGN.md Section 4).

A second, stricter layout serves the mesh-parallel decode engine
(``serve=True`` / ``decode=True``, consumed by runtime.mesh_serve —
DESIGN.md Section 10): every GEMM weight shards its **output (N) axis
only** on "model" (contraction dims never split, so no partial-sum
collectives reorder the reduction and sharded logits stay bit-identical
to the single-device trace), embeddings shard the vocab axis (the tied
unembed transpose then also contracts locally), and the slot-pool cache
arena shards its batch axis over the dp axes plus its *head* axes on
"model" — head axes are batch-like (per-head independence), so sharding
them is also reduction-order-free.  Metadata stays replicated in both
layouts.

Divisibility is not required for correctness (GSPMD pads), but rules avoid
padding where it matters; `_divides` guards the places XLA would waste.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# parameter-name classification
_IN_OUT = ("wq", "wk", "wv", "w_gate", "w_up", "w_ff1", "w_x", "router",
           "head", "w_rg", "w_ig", "wz", "wi", "wf", "wo_gate")
_OUT_IN = ("wo", "w_down", "w_ff2", "w_out")
_REPLICATE = ("ln", "ln1", "ln2", "ln_x", "gn", "final_norm", "enc_norm",
              "lam", "qn", "kn", "ln1_b", "ln2_b", "final_norm_b")
# GriffinWeights (block-compacted weights) pytree children.  The compacted
# K axis (b_comp rows) is never sharded: kidx holds *global* K-block ids and
# per-shard counts would diverge, so only the output (N) axis splits; the
# scalar-prefetch metadata is tiny and rides along replicated
# (DESIGN.md Section 4).
_GRIFFIN_META = ("kidx", "cnt", "inv_perm")


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _divides(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[a] for a in name]))
    return mesh.shape[name]


def param_spec(path: str, leaf, mesh: Mesh, fsdp: bool = True,
               ep: bool = False, serve: bool = False) -> P:
    """PartitionSpec for one parameter leaf, by trailing name + rank.

    ``ep=True`` shards MoE expert weights (L, E, D, F) with the *expert*
    axis on "model" (expert parallelism: token all-to-alls instead of
    expert-weight gathers) rather than TP-within-expert on F.

    ``serve=True`` selects the decode-serving layout (DESIGN.md
    Section 10): output-axis-only TP — every GEMM weight (including the
    ``_OUT_IN`` projections that train-time TP shards on their input dim)
    puts its last (output) axis on "model" and nothing on "data", and
    embeddings shard the vocab axis so the tied-unembed transpose keeps
    its contraction local.  No contraction dim is ever split, so the
    sharded compute is a reduction-order-preserving rearrangement of the
    single-device compute.
    """
    name = path.rstrip("']").split("'")[-1] if "'" in path else path
    rank = len(leaf.shape)
    data_ax = "data" if (fsdp and not serve
                         and "data" in mesh.axis_names) else None
    child = name.rsplit(".", 1)[-1] if "." in name else ""
    if child in _GRIFFIN_META:
        return P(*([None] * rank))
    if child == "b_comp":
        # parent GEMM name decides which mesh axis the output (N) dim gets;
        # in the serving layout every parent's output axis goes to "model"
        # (the compacted K rows are never split in either layout)
        parent = path[:path.rfind(".")]
        pname = parent.rstrip("']").split("'")[-1] if "'" in parent else parent
        if serve:
            ax = "model" if pname in _IN_OUT + _OUT_IN else None
        else:
            ax = "model" if pname in _IN_OUT else \
                (data_ax if pname in _OUT_IN else None)
        return _checked(P(*([None] * (rank - 1) + [ax])), leaf, mesh)
    if name in _REPLICATE or rank <= 1:
        return P()
    if ep and rank == 4 and name in ("w_gate", "w_up", "w_down") \
            and "moe" in path:
        # (L, E, D, F) or (L, E, F, D): experts over "model", in-dim FSDP
        return _checked(P(None, "model", data_ax, None), leaf, mesh)
    if name == "embed":
        spec = ["model", None] if serve else ["model", data_ax]
    elif name == "conv":
        spec = [None, "model"]
    elif name in ("rz", "ri", "rf", "ro") or (name in ("wq", "wk", "wv")
                                              and rank >= 3
                                              and leaf.shape[-1] == leaf.shape[-2]):
        # per-head block-diagonal mats (H, hd, hd)
        spec = [None] * (rank - 1) + ["model"]
        return _checked(P(*spec), leaf, mesh)
    elif name in _IN_OUT:
        spec = [None] * (rank - 2) + [data_ax, "model"]
    elif name in _OUT_IN:
        spec = ([None] * (rank - 2) + [None, "model"] if serve
                else [None] * (rank - 2) + ["model", data_ax])
    else:
        spec = [None] * rank
    return _checked(P(*spec), leaf, mesh)


def _checked(spec: P, leaf, mesh: Mesh) -> P:
    """Drop axes whose dim is not divisible by the mesh axis: jit input
    shardings require exact divisibility (internal constraints would pad)."""
    out = []
    for dim, ax in zip(leaf.shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = _axis_size(mesh, ax)
        out.append(ax if (dim >= size and dim % size == 0) else None)
    return P(*out)


def shard_params(params_shape: Any, mesh: Mesh, fsdp: bool = True,
                 ep: bool = False, serve: bool = False) -> Any:
    """NamedSharding tree for a (ShapeDtypeStruct or array) param tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [NamedSharding(mesh, param_spec(jax.tree_util.keystr(p), leaf,
                                            mesh, fsdp, ep, serve))
             for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_spec(leaf, mesh: Mesh) -> P:
    """Activations/inputs: batch over dp axes; fall back to the sequence
    axis when the batch doesn't divide (e.g. long_500k batch=1)."""
    dp = dp_axes(mesh)
    dpn = _axis_size(mesh, dp)
    shape = leaf.shape
    if len(shape) == 0:
        return P()
    if _divides(shape[0], dpn):
        return P(dp, *([None] * (len(shape) - 1)))
    if len(shape) >= 2 and _divides(shape[1], dpn):
        return P(None, dp, *([None] * (len(shape) - 2)))
    return P(*([None] * len(shape)))


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_spec(leaf, mesh)), batch)


def cache_spec(path: str, leaf, mesh: Mesh, batch: int,
               decode: bool = False, heads: int = 0,
               paged: frozenset = frozenset()) -> P:
    """KV caches and recurrent state.

    Default (train/long-context) layout: batch dim -> dp axes; the
    *sequence* axis (longest remaining divisible dim) -> 'model'.
    Sequence-sharding the cache keeps per-chip capacity (a command-r
    decode_32k cache is ~1 TB) while decode attention reduces tiny (B, H)
    softmax partials instead of all-gathering the cache — the
    head_dim-sharded layout all-gathered the full cache every step
    (EXPERIMENTS.md Section Perf, iteration 4).  Batch-1 long-context cells
    shard the sequence over dp as well.

    ``decode=True`` is the slot-pool arena layout of the mesh-parallel
    serving engine (runtime.mesh_serve, DESIGN.md Section 10): the batch
    (slot) axis shards over the dp axes — including rank-1 per-slot
    position/state counters, the promoted ``(B,)`` vectors of
    runtime.engine — and axes whose extent equals ``heads`` (KV heads of
    attention caches, mLSTM/sLSTM head axes) shard on "model".  Head axes
    are batch-like — no reduction ever crosses them — and the last axis
    (head_dim / feature, a contraction dim in decode attention and the
    recurrent cell updates) is deliberately never split, so sharded decode
    stays a reduction-order-preserving rearrangement of the single-device
    step.  Sequence stays whole: per-slot ``dynamic_update_slice`` writes
    land at runtime-variable positions, and splitting them would turn every
    cache write into cross-device traffic.
    """
    dp = dp_axes(mesh)
    dpn = _axis_size(mesh, dp)
    mdl = mesh.shape.get("model", 1)
    shape = leaf.shape
    spec: list = [None] * len(shape)
    if len(shape) == 0:
        return P()
    # paged-arena leaves (runtime/paging.py) never match the slot-batch
    # scan below — the page table is (num_slots, max_pages) and a pool's
    # first data axis is num_pages — so they are classified by name before
    # it: the table replicates (every shard gathers with the same ids),
    # pools shard their *page* axis over dp (pages are batch-like: no
    # reduction crosses them) plus the KV-head axis on "model" in the
    # decode layout, and scale vectors follow their pool's page axis.
    if paged and "'" in path:
        name = path.rstrip("']").split("'")[-1]
        if name == "pages":
            return P(*spec)
        base = name[:-6] if name.endswith("_scale") else name
        if base in paged:
            if len(shape) >= 2 and _divides(shape[1], dpn):
                spec[1] = dp
            if decode and not name.endswith("_scale") and mdl > 1 \
                    and heads > 0 and _divides(heads, mdl):
                for i in range(len(shape) - 2, 1, -1):
                    if shape[i] == heads:
                        spec[i] = "model"
                        break
            return P(*spec)
    placed_dp = None
    for i, d in enumerate(shape):
        if d == batch and _divides(d, dpn):
            spec[i] = dp
            placed_dp = i
            break
    if decode:
        if mdl > 1 and heads > 0 and _divides(heads, mdl):
            # scan from the tail (skipping the last, contraction-bearing
            # axis): head axes sit rightmost in every family's cache
            # layout, so when a leading layer/sequence axis coincidentally
            # equals ``heads`` (e.g. cache_len == num_kv_heads) the real
            # head axis still wins and sequence stays whole
            for i in range(len(shape) - 2, -1, -1):
                if i != placed_dp and shape[i] == heads:
                    spec[i] = "model"
                    break
        return P(*spec)
    if placed_dp is None:
        # batch too small: shard the longest divisible axis (the KV seq)
        cand = [(d, i) for i, d in enumerate(shape[:-1])
                if _divides(d, dpn) and d >= dpn]
        if cand:
            placed_dp = max(cand)[1]
            spec[placed_dp] = dp
    if mdl > 1:
        cand = [(d, i) for i, d in enumerate(shape)
                if i != placed_dp and spec[i] is None
                and _divides(d, mdl) and d >= 8 * mdl]
        if cand:
            spec[max(cand)[1]] = "model"
    return P(*spec)


def shard_cache(cache: Any, mesh: Mesh, batch: int,
                decode: bool = False, heads: int = 0,
                paged: frozenset = frozenset()) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = [NamedSharding(mesh, cache_spec(jax.tree_util.keystr(p), leaf,
                                            mesh, batch, decode, heads,
                                            paged))
             for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# per-shard kernel operand specs (shard_map, DESIGN.md Section 10)
# ---------------------------------------------------------------------------
# Under the serving layout every device's GEMM is fully local, so
# ``griffin_linear`` wraps the real Pallas kernels in ``shard_map``.  The
# (in_specs, out_spec) each kernel call uses are defined next to the
# shard-local entry points in the kernel packages (one definition, used by
# dispatch and tests alike); these re-exports are the layout-rule layer's
# view of them, plus the shardability predicate that decides kernel vs
# decompaction-oracle per weight leaf.

def spmm_shard_specs(axis: str = "model"):
    """shard_map specs for ``griffin_matmul_shard``: activations and the
    global column perm replicated; b_comp split on padded-N; kidx/cnt
    split on their N-tile axis; output split on N.  Matches
    ``param_spec(serve=True)``: b_comp's stored sharding IS the kernel's
    in_spec, so entering the shard_map moves no weight bytes."""
    from ..kernels.griffin_spmm.ops import shard_specs
    return shard_specs(axis)


def gemm_shard_specs(axis: str = "model"):
    """shard_map specs for the dense-weight kernels
    (``sparse_a_matmul_shard`` / ``dense_matmul_shard``): only the weights
    and output split, on N; activations and the per-M-tile runtime
    metadata replicate."""
    from ..kernels.sparse_a.ops import shard_specs
    return shard_specs(axis)


def kernel_shardable(leaf, mesh: Mesh, axis: str = "model") -> bool:
    """Can this GEMM weight leaf (a ``GriffinWeights`` or a plain matrix)
    run the real kernel under shard_map on ``mesh``?  The same predicate
    ``models.common.griffin_linear`` applies at dispatch time: compacted
    weights need their N tiles to split evenly over the model axis; dense
    weights only need their output dim to (each shard re-pads locally)."""
    from ..kernels.dense_gemm import ops as dense_ops
    from ..kernels.griffin_spmm import ops as spmm_ops
    if axis not in mesh.axis_names:
        return False
    mp = mesh.shape[axis]
    if isinstance(leaf, spmm_ops.GriffinWeights):
        return spmm_ops.shardable(leaf, mp)
    return dense_ops.shardable(leaf, mp)
