"""Public ops for Griffin sparse execution on TPU.

``preprocess_weights`` is the paper's offline B preprocessing (Fig. 2/3
step 1) at TPU block granularity; ``balance_columns`` is the load-balancing
shuffle; ``griffin_matmul`` executes; ``auto_matmul`` is the hybrid-morphing
entry point that picks dense / Sparse.A / Sparse.B / dual per call
(core.hybrid.select_mode — the same policy the framework layer applies per
GEMM through models.common.griffin_linear).

``GriffinWeights`` is a registered pytree: compacted weights flow through
jit, ``lax.scan`` over stacked layers, and the sharding rules in
runtime.sharding (DESIGN.md Section 4).  ``stack_weights`` builds the
stacked (leading layer/expert axis) form the model stacks consume;
indexing a stacked instance (``gw[i]``) slices every array leaf.
Preprocessing and stacking return host numpy leaves: the serving engines
place them once, each shard straight onto its own device, so a compacted
model never has to fit on one chip first.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.hybrid import select_mode
from ...core.spec import Mode
from ..dense_gemm.ops import dense_matmul
from ..sparse_a.ops import sparse_a_matmul
from .kernel import griffin_spmm_kernel

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_N = 128


@dataclasses.dataclass
class GriffinWeights:
    """Block-compacted weight representation + metadata (device arrays).

    Array fields may carry extra leading axes (stacked layers / experts);
    the trailing axes are always the single-matrix layout documented here.
    """

    b_comp: jax.Array        # (..., max_cnt*block_k, N_padded)
    kidx: jax.Array          # (..., n_tiles, max_cnt) int32
    cnt: jax.Array           # (..., n_tiles) int32
    inv_perm: Optional[jax.Array]    # (..., N_padded) undo of the balance
    #                                  shuffle's column permutation (None =
    #                                  identity / balancing disabled)
    k: int                   # original K (padded)
    n: int                   # original N (unpadded)
    block_k: int
    block_n: int
    # Per-GEMM Mode-selection threshold override from a tuned kernel plan
    # (repro.tuning, DESIGN.md Section 12): when set, griffin_linear passes
    # it as ``select_mode``'s A threshold for this GEMM instead of the
    # scope-wide one.  A meta field (trace-time constant): the threshold
    # picks *which* kernel configuration runs, never what it computes.
    a_thr: Optional[float] = None

    @property
    def density(self) -> float:
        """Fraction of surviving (bk x bn) blocks.  Memoized per instance:
        the computation device-syncs ``cnt``, and callers walk it per GEMM
        leaf (``runtime.engine.weight_sparsity`` at every engine
        construction).  The memo lives in ``__dict__`` — not a dataclass
        field, so pytree flatten/unflatten (which rebuilds instances from
        the registered fields only) neither carries a stale value onto
        tree-mapped copies nor breaks; fresh instances recompute lazily."""
        memo = self.__dict__.get("_density_memo")
        if memo is None:
            total_blocks = (self.k // self.block_k) * \
                int(np.prod(self.cnt.shape))
            memo = float(np.asarray(self.cnt).sum()) / max(total_blocks, 1)
            self.__dict__["_density_memo"] = memo
        return memo

    @property
    def compaction(self) -> float:
        """Grid-depth compaction vs dense: max_cnt / nb_k (lower is better)."""
        return self.kidx.shape[-1] / (self.k // self.block_k)

    def __getitem__(self, i) -> "GriffinWeights":
        """Slice a stacked instance along its leading axis."""
        return jax.tree.map(lambda a: a[i], self)


jax.tree_util.register_dataclass(
    GriffinWeights,
    data_fields=["b_comp", "kidx", "cnt", "inv_perm"],
    meta_fields=["k", "n", "block_k", "block_n", "a_thr"])


def balance_columns(w_padded: np.ndarray, block_k: int, block_n: int,
                    unit: int) -> np.ndarray:
    """Unit-column permutation: the paper's load-balancing shuffle at tile
    granularity.

    A kernel N tile spans ``block_n / unit`` pruning units; a K block of the
    tile survives if *any* of its units is nonzero there, so the grid depth
    is the max over tiles of the union pattern size.  Grouping units with
    *similar* K patterns (lexicographic sort of their block-mask bitmaps)
    keeps unions tight and equalizes counts.  Returns a column permutation.
    """
    pk, pn = w_padded.shape
    nb_k = pk // block_k
    nu = pn // unit
    # unit pattern bitmap: (nu, nb_k)
    pat = (w_padded.reshape(nb_k, block_k, nu, unit) != 0).any(axis=(1, 3)).T
    order = np.lexsort(pat.T[::-1])          # cluster similar patterns
    perm = (order[:, None] * unit + np.arange(unit)[None, :]).reshape(-1)
    return perm


def preprocess_weights(w: np.ndarray, *, block_k: int = DEFAULT_BLOCK_K,
                       block_n: int = DEFAULT_BLOCK_N,
                       balance: bool = True,
                       unit: Optional[int] = None) -> GriffinWeights:
    """Offline B preprocessing: drop all-zero (bk x bn) blocks, build the
    per-N-tile metadata, optionally balance unit-columns across tiles.

    ``unit`` is the pruning granularity along N (defaults to block_n / 4,
    min 8): weights are expected pruned in (block_k x unit) blocks, e.g. by
    repro.sparsity.block_prune.
    """
    w = np.asarray(w)
    k, n = w.shape
    pk = -(-k // block_k) * block_k
    pn = -(-n // block_n) * block_n
    wp = w
    if (pk, pn) != (k, n):
        wp = np.zeros((pk, pn), dtype=w.dtype)
        wp[:k, :n] = w
    nb_k, nb_n = pk // block_k, pn // block_n
    unit = unit or max(8, block_n // 4)

    inv_perm = None
    if balance and pn > block_n and pn % unit == 0:
        full_perm = balance_columns(wp, block_k, block_n, unit)
        # the permutation moves whole unit-wide column groups: gather them
        # as contiguous chunks (a per-column gather is several times
        # slower on published-width matrices)
        order = full_perm[::unit] // unit
        wp = np.take(wp.reshape(pk, pn // unit, unit), order,
                     axis=1).reshape(pk, pn)
        inv_perm = np.argsort(full_perm).astype(np.int32)

    blk_nz = (wp.reshape(nb_k, block_k, nb_n, block_n) != 0).any(axis=(1, 3))
    cnt = blk_nz.sum(axis=0).astype(np.int32)                 # (nb_n,)
    max_cnt = max(int(cnt.max()), 1)
    kidx = np.zeros((nb_n, max_cnt), dtype=np.int32)
    b_comp = np.zeros((max_cnt * block_k, pn), dtype=w.dtype)
    for j in range(nb_n):
        ks = np.flatnonzero(blk_nz[:, j])
        kidx[j, :len(ks)] = ks
        if len(ks) < max_cnt:                                 # clamp padding
            kidx[j, len(ks):] = ks[-1] if len(ks) else 0
        for kc, kb in enumerate(ks):
            b_comp[kc * block_k:(kc + 1) * block_k,
                   j * block_n:(j + 1) * block_n] = \
                wp[kb * block_k:(kb + 1) * block_k,
                   j * block_n:(j + 1) * block_n]
    return GriffinWeights(b_comp=b_comp, kidx=kidx, cnt=cnt,
                          inv_perm=inv_perm, k=pk, n=n,
                          block_k=block_k, block_n=block_n)


def stack_weights(gws: Sequence[GriffinWeights]) -> GriffinWeights:
    """Stack per-layer/per-expert compacted weights along a new leading
    axis, padding every member to the common (max over members) grid depth
    so the stacked leaves are rectangular — the layout ``lax.scan`` and the
    unrolled layer loop both consume.  Host numpy in, host numpy out."""
    assert gws, "empty stack"
    g0 = gws[0]
    for g in gws[1:]:
        assert (g.k, g.n, g.block_k, g.block_n, g.a_thr) == \
            (g0.k, g0.n, g0.block_k, g0.block_n, g0.a_thr), \
            "heterogeneous stack"
        assert (g.inv_perm is None) == (g0.inv_perm is None), \
            "mixed balanced/unbalanced stack"
    max_cnt = max(g.kidx.shape[-1] for g in gws)
    bk = g0.block_k

    def padded(g: GriffinWeights):
        pad_c = max_cnt - g.kidx.shape[-1]
        kidx, b_comp = np.asarray(g.kidx), np.asarray(g.b_comp)
        if pad_c:
            # dead entries (kc >= cnt) — clamp-repeat the last id, zero data
            kidx = np.concatenate(
                [kidx, np.repeat(kidx[:, -1:], pad_c, axis=1)], axis=1)
            b_comp = np.concatenate(
                [b_comp, np.zeros((pad_c * bk, b_comp.shape[1]),
                                  b_comp.dtype)], axis=0)
        return kidx, b_comp

    ks, bs = zip(*[padded(g) for g in gws])
    return GriffinWeights(
        b_comp=np.stack(bs), kidx=np.stack(ks),
        cnt=np.stack([np.asarray(g.cnt) for g in gws]),
        inv_perm=(None if g0.inv_perm is None
                  else np.stack([np.asarray(g.inv_perm) for g in gws])),
        k=g0.k, n=g0.n, block_k=g0.block_k, block_n=g0.block_n,
        a_thr=g0.a_thr)


@functools.partial(jax.jit, static_argnames=("block_m", "dual", "interpret",
                                             "block_k", "block_n", "n"))
def _run(a, b_comp, kidx, cnt, inv_perm, *, block_m, block_k, block_n, n,
         dual, interpret):
    out = griffin_spmm_kernel(a, b_comp, kidx, cnt, block_m=block_m,
                              block_k=block_k, block_n=block_n, dual=dual,
                              interpret=interpret)
    if inv_perm is not None:
        out = out[:, inv_perm]
    return out[:, :n]


# ---------------------------------------------------------------------------
# shard-local execution (SPMD via shard_map, DESIGN.md Section 10)
# ---------------------------------------------------------------------------

def griffin_matmul_shard(a, b_comp, kidx, cnt, *, block_m: int, block_k: int,
                         block_n: int, dual: bool = False,
                         interpret: bool = False) -> jax.Array:
    """Shard-local kernel entry: the raw griffin_spmm kernel on one
    device's slice of the compacted operands.

    ``a`` is the whole (padded) activation — replicated, because ``kidx``
    holds *global* K-block ids and the serving layout never splits the
    contraction dim.  ``b_comp``/``kidx``/``cnt`` are pre-sliced along the
    N-tile axis (``shard_specs``): a contiguous group of N tiles with their
    own metadata rows is a complete, self-contained kernel problem, so the
    per-shard call is literally the unsharded kernel on a narrower grid —
    zero in-kernel collectives.  The balance shuffle's ``inv_perm`` gather
    and the ``[:, :n]`` unpad are *global* column operations and stay with
    the caller (``griffin_matmul``).
    """
    return griffin_spmm_kernel(a, b_comp, kidx, cnt, block_m=block_m,
                               block_k=block_k, block_n=block_n, dual=dual,
                               interpret=interpret)


def shard_specs(axis: str = "model"):
    """(in_specs, out_spec) partitioning ``griffin_matmul_shard``'s
    operands over mesh axis ``axis``: activations replicated, ``b_comp``
    split on its padded-N (last) axis, ``kidx``/``cnt`` split on their
    N-tile (first) axis, output split on N.  Exposed (and re-exported by
    ``runtime.sharding``) so tests and the layout rules agree on one
    definition of the per-shard operand layout."""
    from jax.sharding import PartitionSpec as P
    return (P(), P(None, axis), P(axis, None), P(axis)), P(None, axis)


def shardable(gw: GriffinWeights, n_shards: int) -> bool:
    """True when the compacted operands split evenly into ``n_shards``
    whole-N-tile groups — the condition for the shard_map path.  A stacked
    instance is never shardable at the op level (the engine slices per
    layer inside its scan)."""
    if gw.b_comp.ndim != 2 or n_shards < 1:
        return False
    n_tiles = gw.kidx.shape[0]
    return n_tiles % n_shards == 0


def _shard_map_run(ap, gw: GriffinWeights, mesh, axis, *, block_m, dual,
                   interpret):
    in_specs, out_spec = shard_specs(axis)
    local = functools.partial(
        griffin_matmul_shard, block_m=block_m, block_k=gw.block_k,
        block_n=gw.block_n, dual=dual, interpret=interpret)
    # check_vma=False: pallas_call has no replication rule either — the
    # out_spec states the (easily checked) fact that shards are disjoint
    out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                        out_specs=out_spec, check_vma=False)(
                        ap, gw.b_comp, gw.kidx, gw.cnt)
    if gw.inv_perm is not None:
        out = out[:, gw.inv_perm]
    return out[:, :gw.n]


def decompact_weights(gw: GriffinWeights) -> jax.Array:
    """jnp reconstruction of the (padded K, n) block-pruned dense matrix a
    single (non-stacked) ``GriffinWeights`` denotes — the spec-respecting
    SPMD fallback's weight operand (DESIGN.md Section 10).

    Pure jnp (one-hot scatter of the compacted blocks back to their global
    K rows, then the balance shuffle's inverse column permutation), so it
    traces under jit and GSPMD can partition it where ``pallas_call`` —
    which has no SPMD partitioning rule — cannot run at all.  Clamp-padded
    dead ``kidx`` entries duplicate a live block id but their ``b_comp``
    rows are zero, so the scatter-add contributes nothing for them.
    Surviving values are reconstructed exactly (preprocessing never changes
    them), hence ``a @ decompact_weights(gw)`` is bit-equal to the dense
    product with the block-pruned weights.
    """
    assert gw.b_comp.ndim == 2, "decompact a per-layer slice, not a stack"
    bk = gw.block_k
    nb_k = gw.k // bk
    nt, mc = gw.kidx.shape
    pn = gw.b_comp.shape[-1]
    bn = pn // nt
    bc = gw.b_comp.reshape(mc, bk, nt, bn)                    # (c, r, t, s)
    onehot = jax.nn.one_hot(gw.kidx, nb_k, dtype=gw.b_comp.dtype)
    w = jnp.einsum("crts,tcK->Krts", bc, onehot)              # (K, r, t, s)
    w = w.reshape(nb_k * bk, pn)
    if gw.inv_perm is not None:
        w = w[:, gw.inv_perm]
    return w[:, :gw.n]


def griffin_matmul(a: jax.Array, gw: GriffinWeights, *,
                   block_m: int = DEFAULT_BLOCK_M, dual: bool = False,
                   interpret: bool = False, spmd: bool = False,
                   mesh=None, mesh_axis: str = "model") -> jax.Array:
    """C = A @ W_pruned from the compacted representation.

    ``mesh`` (a ``jax.sharding.Mesh``) runs the **real kernel under SPMD**
    via ``shard_map`` (DESIGN.md Section 10): every device executes
    ``griffin_matmul_shard`` on its whole-N-tile slice of
    b_comp/kidx/cnt against the replicated activations — bit-identical to
    the unsharded kernel (same per-tile fp32 accumulation order), with
    zero in-kernel collectives.  Requires ``shardable(gw,
    mesh.shape[mesh_axis])``; callers (``models.common.griffin_linear``)
    check and fall back to ``spmd=True`` otherwise.

    ``spmd=True`` is the decompaction **oracle** (previously the only
    multi-device path): reconstruct the denoted block-pruned dense matrix
    and take a plain jnp dot, which GSPMD shards along the weights'
    output (N) axis without ever splitting the contraction.  Bit-equal to
    the dense product with the pruned weights, allclose (different
    reduction order) to the kernel.  Dual-mode predication is a no-op on
    values (skipped A blocks are exactly zero), so it covers Mode.AB too.
    """
    m, k = a.shape
    if spmd:
        w = decompact_weights(gw)
        return jnp.dot(a, w[:k], preferred_element_type=jnp.float32)
    bm = min(block_m, max(8, -(-m // 8) * 8))
    pm = -(-m // bm) * bm
    ap = jnp.pad(a, ((0, pm - m), (0, gw.k - k)))
    if mesh is not None:
        assert shardable(gw, mesh.shape[mesh_axis]), \
            (gw.kidx.shape, dict(mesh.shape), mesh_axis)
        out = _shard_map_run(ap, gw, mesh, mesh_axis, block_m=bm, dual=dual,
                             interpret=interpret)
        return out[:m]
    out = _run(ap, gw.b_comp, gw.kidx, gw.cnt, gw.inv_perm, block_m=bm,
               block_k=gw.block_k, block_n=gw.block_n, n=gw.n, dual=dual,
               interpret=interpret)
    return out[:m]


def auto_matmul(a: jax.Array, w, gw: Optional[GriffinWeights] = None, *,
                a_sparsity: float = 0.0, b_sparsity: float = 0.0,
                interpret: bool = False) -> jax.Array:
    """Hybrid-morphing entry point (paper Section IV-B at the op level):
    measure/declare tensor sparsity, pick the execution mode, run the same
    core in dense / Sparse.A / Sparse.B / dual configuration.

    Dispatch (every ``core.spec.Mode`` reaches a real kernel):
      DENSE -> dense_gemm;  A -> sparse_a (runtime-compacted A, dense B);
      B -> griffin_spmm;    AB -> griffin_spmm dual (compacted B + on-the-fly
      A-block predication).  Declared-sparse B without preprocessed weights
      falls back dense/Sparse.A — there is nothing compacted to walk.
    """
    mode = select_mode(a_sparsity, b_sparsity)
    if mode in (Mode.B, Mode.AB) and gw is not None:
        return griffin_matmul(a, gw, dual=(mode == Mode.AB),
                              interpret=interpret)
    if mode in (Mode.A, Mode.AB):
        return sparse_a_matmul(a, w, interpret=interpret)
    return dense_matmul(a, w, interpret=interpret)
