"""Jit'd public wrapper for the dense GEMM kernel (padding + defaults)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import dense_matmul_kernel

# MXU-aligned defaults for TPU v5e; interpret mode (CPU validation) uses the
# same shapes so the BlockSpec logic is exercised identically.
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 128


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def _dense_matmul_jit(a: jax.Array, b: jax.Array, *, block_m, block_n,
                      block_k, interpret) -> jax.Array:
    m, n = a.shape[0], b.shape[1]
    bm, bn, bk = (min(block_m, _rup(m)), min(block_n, _rup(n)),
                  min(block_k, _rup(a.shape[1])))
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    out = dense_matmul_kernel(ap, bp, block_m=bm, block_n=bn, block_k=bk,
                              interpret=interpret)
    return out[:m, :n]


def dense_matmul_shard(a, b, *, block_m: int, block_n: int, block_k: int,
                       interpret: bool = False) -> jax.Array:
    """Shard-local kernel entry: the blocked dense kernel on one device's
    N-slice of ``b`` against the replicated (whole-K) activations — each
    shard pads its slice to its own grid and unpads after, mirroring
    ``sparse_a_matmul_shard``."""
    m, n_local = a.shape[0], b.shape[1]
    bm, bn, bk = (min(block_m, _rup(m)), min(block_n, _rup(n_local)),
                  min(block_k, _rup(a.shape[1])))
    out = dense_matmul_kernel(_pad_to(a, bm, bk), _pad_to(b, bk, bn),
                              block_m=bm, block_n=bn, block_k=bk,
                              interpret=interpret)
    return out[:m, :n_local]


def shardable(b, n_shards: int) -> bool:
    """True when the weights' output axis splits evenly over the shards."""
    return b.ndim == 2 and n_shards >= 1 and b.shape[1] % n_shards == 0


def dense_matmul(a: jax.Array, b: jax.Array, *,
                 block_m: int = DEFAULT_BLOCK_M,
                 block_n: int = DEFAULT_BLOCK_N,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: bool = False,
                 mesh=None, mesh_axis: str = "model") -> jax.Array:
    """C = A @ B via the Pallas blocked kernel (arbitrary shapes, padded).

    ``mesh`` runs the kernel under SPMD via ``shard_map`` — every device
    executes ``dense_matmul_shard`` on its N-slice of ``b`` with zero
    in-kernel collectives (DESIGN.md Section 10); requires
    ``shardable(b, mesh.shape[mesh_axis])``.
    """
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        assert shardable(b, mesh.shape[mesh_axis]), \
            (b.shape, dict(mesh.shape), mesh_axis)
        local = functools.partial(dense_matmul_shard, block_m=block_m,
                           block_n=block_n, block_k=block_k,
                           interpret=interpret)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P(), P(None, mesh_axis)),
                             out_specs=P(None, mesh_axis),
                             check_vma=False)(a, b)
    return _dense_matmul_jit(a, b, block_m=block_m, block_n=block_n,
                             block_k=block_k, interpret=interpret)


def _rup(x: int, base: int = 8) -> int:
    """Round up to a lane-aligned size so tiny test shapes still tile."""
    return max(base, -(-x // base) * base)
