"""Weight pruning for the Griffin execution paths.

Two granularities:
  - ``magnitude_prune``: unstructured (element) pruning — what the paper's
    cycle model evaluates (the element-granular accelerator skips these).
  - ``block_prune``: (block_k x unit) block pruning by L2 norm — the
    hardware-aware granularity the TPU kernel (griffin_spmm) can exploit:
    a pruned block is exactly zero, so preprocessing drops it.

Both are pure functions usable inside jit; ``PruneSchedule`` ramps sparsity
during training (cubic schedule, Zhu & Gupta 2017 [73] — the paper's own
pruning reference).

``sparsify_params`` is the model-stack entry point (DESIGN.md Section 4):
it block-prunes the weight GEMM leaves of a parameter pytree and replaces
them with block-compacted ``GriffinWeights`` the framework layer
(``models.common.griffin_linear``) executes through the Sparse.B kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def magnitude_prune(w: jax.Array, sparsity: float) -> jax.Array:
    """Zero the smallest-|w| fraction ``sparsity`` of entries."""
    if sparsity <= 0.0:
        return w
    k = max(1, int(round(w.size * (1.0 - sparsity))))
    thresh = jnp.sort(jnp.abs(w).reshape(-1))[-k]
    return jnp.where(jnp.abs(w) >= thresh, w, 0).astype(w.dtype)


def block_prune(w: jax.Array, sparsity: float, block_k: int = 128,
                unit: int = 32) -> jax.Array:
    """Zero the lowest-L2 fraction ``sparsity`` of (block_k x unit) blocks.

    Shapes not divisible by the block are handled by zero padding (the pad
    never changes block norms).
    """
    if sparsity <= 0.0:
        return w
    k, n = w.shape
    pk, pn = -(-k // block_k) * block_k, -(-n // unit) * unit
    # aligned weights (every published width) skip the padded copy, which
    # would also land a sharded weight whole on the default device
    wp = w if (pk, pn) == (k, n) else \
        jnp.zeros((pk, pn), w.dtype).at[:k, :n].set(w)
    nb_k, nb_n = pk // block_k, pn // unit
    blocks = wp.reshape(nb_k, block_k, nb_n, unit)
    norms = jnp.sqrt((blocks.astype(jnp.float32) ** 2).sum(axis=(1, 3)))
    nkeep = max(1, int(round(norms.size * (1.0 - sparsity))))
    thresh = jnp.sort(norms.reshape(-1))[-nkeep]
    keep = (norms >= thresh)[:, None, :, None]
    return (blocks * keep).reshape(pk, pn)[:k, :n].astype(w.dtype)


def sparsity_of(x: jax.Array) -> jax.Array:
    """Fraction of exact zeros (the quantity Table IV reports)."""
    return jnp.mean((x == 0).astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class PruneSchedule:
    """Cubic sparsity ramp s(t) = s_f * (1 - (1 - t/T)^3) on [t0, t0+T]."""

    final_sparsity: float
    begin_step: int = 0
    ramp_steps: int = 1000
    block_k: int = 0          # 0 => unstructured magnitude pruning
    unit: int = 32

    def sparsity_at(self, step: jax.Array) -> jax.Array:
        t = jnp.clip((step - self.begin_step) / max(self.ramp_steps, 1), 0, 1)
        return self.final_sparsity * (1.0 - (1.0 - t) ** 3)

    def apply(self, w: jax.Array, step: int) -> jax.Array:
        """Host-side application at checkpoint boundaries (the ramp changes
        the threshold, so this is applied outside jit per ramp milestone).
        Stacked layer weights (L, ..., in, out) are pruned per layer."""
        s = float(self.sparsity_at(jnp.asarray(step)))
        fn = (lambda x: block_prune(x, s, min(self.block_k, x.shape[0]),
                                    min(self.unit, x.shape[1]))) \
            if self.block_k else (lambda x: magnitude_prune(x, s))
        if w.ndim == 2:
            return fn(w)
        lead = w.shape[:-2]
        flat = w.reshape((-1,) + w.shape[-2:])
        out = jax.vmap(fn)(flat)
        return out.reshape(lead + w.shape[-2:])


# ---------------------------------------------------------------------------
# model-stack sparsification
# ---------------------------------------------------------------------------

# Trailing param names of the weight GEMMs griffin_linear executes.  Per-head
# block-diagonal mats (xlstm rz/ri/...) and the recurrent-state path are NOT
# listed: they are not weight GEMMs (DESIGN.md Section 7, deviations).  The
# sLSTM gate projections wz/wi/wf/wo are (D, D) GEMMs and all four are
# listed; the same-named mLSTM gate vectors (din, H) fall under min_dim.
GEMM_WEIGHTS: Tuple[str, ...] = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_ff1", "w_ff2",
    "wz", "wi", "wf", "head")

# Subtrees whose wq/wk/wv are per-head *block-diagonal* (H, hd, hd) mats
# consumed by einsum, not weight GEMMs: mLSTM q/k/v (models.xlstm).
_BLOCKDIAG_PARENTS: Tuple[str, ...] = ("m_blocks",)


def sparsify_params(params: Any, sparsity: float, *, block_k: int = 128,
                    block_n: int = 128, unit: Optional[int] = None,
                    names: Sequence[str] = GEMM_WEIGHTS,
                    min_dim: int = 32, balance: bool = True,
                    compact: bool = True) -> Any:
    """Block-prune the weight GEMM leaves of a parameter pytree.

    With ``compact=True`` each pruned leaf is replaced by a block-compacted
    ``GriffinWeights`` (stacked leaves — layer stacks, MoE experts — get a
    stacked GriffinWeights whose members share a padded common grid depth);
    with ``compact=False`` the pruned weights stay plain zero-carrying
    arrays, which is the bit-exact dense reference for the compacted run
    (``bench_e2e`` compares the two).

    Selection is by trailing param name (``names``) and minimum GEMM dims
    (``min_dim`` — tiny projections like mLSTM gate vectors are skipped:
    metadata would outweigh the blocks).  Norm scales, embeddings and
    per-head block-diagonal mats are never touched.
    """
    from ..kernels.griffin_spmm.ops import preprocess_weights, stack_weights

    def convert(w: jax.Array):
        bk = min(block_k, w.shape[-2])
        bn = min(block_n, w.shape[-1])
        un = min(unit or max(8, bn // 4), w.shape[-1])

        def one(m):
            return block_prune(m, sparsity, bk, un)

        def pre(m):
            return preprocess_weights(np.asarray(m), block_k=bk, block_n=bn,
                                      unit=un, balance=balance)

        if w.ndim == 2:
            wp = one(w)
            if not compact:
                return wp
            return pre(wp)
        lead = w.shape[:-2]
        flat = w.reshape((-1,) + w.shape[-2:])
        if flat.shape[0] == 0:
            # zero-length layer stack (stack_layers(n=0), e.g. the reduced
            # hybrid's empty tail): nothing to compact, and scan over the
            # length-0 xs is a no-op either way — keep the empty leaf
            return w
        slices = [one(flat[i]) for i in range(flat.shape[0])]
        if not compact:
            return jnp.stack(slices).reshape(w.shape)
        gw = stack_weights([pre(s) for s in slices])
        if len(lead) > 1:                     # e.g. (G, n_m) xlstm groups
            gw = jax.tree.map(
                lambda a: a.reshape(lead + a.shape[1:]), gw)
        return gw

    def walk(tree, name="", path=()):
        if isinstance(tree, dict):
            return {k: walk(v, k, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, name, path) for v in tree)
        blockdiag = name in ("wq", "wk", "wv") and \
            any(p in _BLOCKDIAG_PARENTS for p in path)
        if name in names and not blockdiag and hasattr(tree, "ndim") \
                and tree.ndim >= 2 \
                and tree.shape[-2] >= min_dim and tree.shape[-1] >= min_dim:
            return convert(tree)
        return tree

    return walk(params)
