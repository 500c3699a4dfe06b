"""Public ops for Sparse.A (activation-sparse) execution on TPU.

``compact_activations`` builds the runtime metadata — the A-side analogue of
griffin_spmm's offline ``preprocess_weights``, except nothing is known until
the activations exist, so compaction happens per call:

  - on **concrete** arrays (op level, serving with host-visible tensors) the
    metadata is built in numpy and ``max_cnt`` is the true maximum live
    count, so the kernel grid physically shrinks (real compaction);
  - on **traced** arrays (inside jit) grid shapes must be static before the
    values exist, so the metadata is built with jnp at the full K depth and
    skipping degrades to trailing predicated no-ops — MXU work is still
    saved, grid depth is not (DESIGN.md Section 5).

``sparse_a_matmul`` pads, compacts, and runs the kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import sparse_a_gemm_kernel

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_N = 128


@dataclasses.dataclass
class ActivationMeta:
    """Per-M-tile live-K-block metadata for one activation matrix."""

    kidx: jax.Array          # (m_tiles, max_cnt) int32
    cnt: jax.Array           # (m_tiles,) int32
    m: int                   # padded M
    k: int                   # padded K
    block_m: int
    block_k: int

    @property
    def density(self) -> float:
        """Fraction of live (block_m x block_k) A blocks (concrete only)."""
        mt, kt = self.m // self.block_m, self.k // self.block_k
        return float(np.asarray(self.cnt).sum()) / max(mt * kt, 1)

    @property
    def compaction(self) -> float:
        """Grid-depth compaction vs dense: max_cnt / k_tiles (lower is
        better; 1.0 when built under jit — static-shape fallback)."""
        return self.kidx.shape[1] / (self.k // self.block_k)


def _rup(x: int, base: int = 8) -> int:
    return max(base, -(-x // base) * base)


def _pad2(x: jax.Array, p0: int, p1: int) -> jax.Array:
    if p0 > x.shape[0] or p1 > x.shape[1]:
        x = jnp.pad(x, ((0, p0 - x.shape[0]), (0, p1 - x.shape[1])))
    return x


def compact_activations(a: jax.Array, *, block_m: int = DEFAULT_BLOCK_M,
                        block_k: int = DEFAULT_BLOCK_K) -> ActivationMeta:
    """Runtime compaction: list the K blocks each M tile must visit.

    Concrete ``a`` -> numpy metadata with the true (minimal) ``max_cnt``;
    traced ``a`` -> jnp metadata at full K depth (static shapes under jit).
    """
    m, k = a.shape
    bm = min(block_m, _rup(m))
    bk = min(block_k, _rup(k))
    pm, pk = -(-m // bm) * bm, -(-k // bk) * bk
    mt, kt = pm // bm, pk // bk
    if isinstance(a, jax.core.Tracer):
        ap = _pad2(a, pm, pk)
        nz = (ap.reshape(mt, bm, kt, bk) != 0).any(axis=(1, 3))   # (mt, kt)
        cnt = nz.sum(axis=1).astype(jnp.int32)
        # stable sort: live blocks first, original k order preserved; dead
        # trailing entries hold valid ids (DMA'd but predicated off).
        kidx = jnp.argsort(~nz, axis=1, stable=True).astype(jnp.int32)
        return ActivationMeta(kidx=kidx, cnt=cnt, m=pm, k=pk,
                              block_m=bm, block_k=bk)
    a_np = np.zeros((pm, pk), dtype=np.asarray(a).dtype)
    a_np[:m, :k] = np.asarray(a)
    nz = (a_np.reshape(mt, bm, kt, bk) != 0).any(axis=(1, 3))
    cnt = nz.sum(axis=1).astype(np.int32)
    max_cnt = max(int(cnt.max()), 1)
    kidx = np.zeros((mt, max_cnt), dtype=np.int32)
    for i in range(mt):
        ks = np.flatnonzero(nz[i])
        kidx[i, :len(ks)] = ks
        if len(ks) < max_cnt:                                     # clamp pad
            kidx[i, len(ks):] = ks[-1] if len(ks) else 0
    return ActivationMeta(kidx=jnp.asarray(kidx), cnt=jnp.asarray(cnt),
                          m=pm, k=pk, block_m=bm, block_k=bk)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def _run(a, b, kidx, cnt, *, block_m, block_k, block_n, interpret):
    return sparse_a_gemm_kernel(a, b, kidx, cnt, block_m=block_m,
                                block_k=block_k, block_n=block_n,
                                interpret=interpret)


# ---------------------------------------------------------------------------
# shard-local execution (SPMD via shard_map, DESIGN.md Section 10)
# ---------------------------------------------------------------------------

def sparse_a_matmul_shard(a, w, kidx, cnt, *, block_m: int, block_k: int,
                          block_n: int, interpret: bool = False) -> jax.Array:
    """Shard-local kernel entry: the raw sparse_a kernel on one device's
    N-slice of the dense weights.

    ``a`` and the runtime-compaction metadata are replicated — the
    metadata is per-*M-tile* (live K blocks of the activations), which an
    output-axis split never touches, so every shard skips exactly the
    same A blocks.  ``w`` arrives pre-sliced on N (``shard_specs``); each
    shard pads its slice up to its own block_n grid and unpads after, so
    uneven tile alignment at the global scale never forces a fallback.
    """
    n_local = w.shape[1]
    bn = min(block_n, _rup(n_local))
    pn = -(-n_local // bn) * bn
    out = sparse_a_gemm_kernel(a, _pad2(w, a.shape[1], pn), kidx, cnt,
                               block_m=block_m, block_k=block_k, block_n=bn,
                               interpret=interpret)
    return out[:, :n_local]


def shard_specs(axis: str = "model"):
    """(in_specs, out_spec) for ``sparse_a_matmul_shard`` over mesh axis
    ``axis``: only the weights (and the output) split, on N; activations
    and per-M-tile metadata replicate."""
    from jax.sharding import PartitionSpec as P
    return (P(), P(None, axis), P(), P()), P(None, axis)


def shardable(w, n_shards: int) -> bool:
    """True when the dense weights' output axis splits evenly (each shard
    re-pads locally, so N-tile alignment is not required)."""
    return w.ndim == 2 and n_shards >= 1 and w.shape[1] % n_shards == 0


def sparse_a_matmul(a: jax.Array, w: jax.Array, *,
                    block_m: int = DEFAULT_BLOCK_M,
                    block_k: int = DEFAULT_BLOCK_K,
                    block_n: int = DEFAULT_BLOCK_N,
                    meta: Optional[ActivationMeta] = None,
                    interpret: bool = False,
                    spmd: bool = False,
                    mesh=None, mesh_axis: str = "model") -> jax.Array:
    """C = A @ W visiting only the live A blocks (Sparse.A execution).

    ``mesh`` runs the **real kernel under SPMD** via ``shard_map``
    (DESIGN.md Section 10): metadata is compacted once (replicated — it is
    per-M-tile and the output-axis split never touches it), then every
    device runs ``sparse_a_matmul_shard`` on its N-slice of ``w`` with
    zero in-kernel collectives.  Requires ``shardable(w,
    mesh.shape[mesh_axis])``.

    ``spmd=True`` is the dense-product oracle (previously the only
    multi-device path): skipped A blocks are exactly zero, so the
    compacted product *is* the plain dense product (``ref.sparse_a_ref``),
    which GSPMD shards along W's output axis.  MXU skipping is forfeited;
    the mode dispatch and jit-set keying upstream stay identical.
    """
    m, k = a.shape
    kw, n = w.shape
    assert k == kw, (k, kw)
    if spmd:
        from .ref import sparse_a_ref
        return sparse_a_ref(a, w)
    if meta is None:
        meta = compact_activations(a, block_m=block_m, block_k=block_k)
    bm, bk = meta.block_m, meta.block_k
    ap = _pad2(a, meta.m, meta.k)
    if mesh is not None:
        assert shardable(w, mesh.shape[mesh_axis]), \
            (w.shape, dict(mesh.shape), mesh_axis)
        in_specs, out_spec = shard_specs(mesh_axis)
        local = functools.partial(sparse_a_matmul_shard, block_m=bm,
                                  block_k=bk, block_n=block_n,
                                  interpret=interpret)
        out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                            out_specs=out_spec, check_vma=False)(
                            ap, _pad2(w, meta.k, n), meta.kidx, meta.cnt)
        return out[:m]
    bn = min(block_n, _rup(n))
    pn = -(-n // bn) * bn
    wp = _pad2(w, meta.k, pn)
    out = _run(ap, wp, meta.kidx, meta.cnt, block_m=bm, block_k=bk,
               block_n=bn, interpret=interpret)
    return out[:m, :n]
