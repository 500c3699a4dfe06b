"""Sparse.A Pallas kernel: compacted activation-sparse GEMM on TPU.

The Sparse.A analogue of griffin_spmm (DESIGN.md Section 3): where Sparse.B
compacts the *weight* matrix offline, here the *iteration space* over A's
K blocks is compacted at runtime.  Per M tile i a metadata list ``kidx[i]``
of K-block ids whose (block_m x block_k) A tile is nonzero, plus a count
``cnt[i]``, is carried as scalar-prefetch operands:

  - the A BlockSpec ``index_map`` dereferences ``kidx`` — the AMUX again,
    now selecting which *activation* tile each multiply consumes;
  - the B BlockSpec dereferences the same metadata, so the dense weight
    matrix is walked in the compacted order (no physical gather of A: the
    data never moves, only the schedule compacts — a zero-copy analogue of
    the paper's A-side zero-mask + arbitration, Fig. 3 steps 2-4);
  - grid position kc >= cnt[i] is predicated off (``pl.when``), so padding
    introduced by ragged per-row counts costs DMA but no MXU work.

Grid: (m_tiles, n_tiles, max_cnt); the k axis is the *compacted* position.
``max_cnt`` is static: when metadata is built from concrete activations
(op level / serving with host-visible tensors) it is the true max count and
the grid physically shrinks; under jit it falls back to the full K depth
with trailing predicated no-ops (DESIGN.md Section 5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ...configs.platform import checked_interpret


def _sparse_a_kernel(kidx_ref, cnt_ref, a_ref, b_ref, o_ref, acc_ref,
                     *, nkc: int):
    i = pl.program_id(0)
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kc < cnt_ref[i])
    def _acc():
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(kc == nkc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sparse_a_gemm_kernel(a: jax.Array, b: jax.Array, kidx: jax.Array,
                         cnt: jax.Array, *, block_m: int, block_k: int,
                         block_n: int, out_dtype=None,
                         interpret: bool = False) -> jax.Array:
    """C = A @ B walking only the K blocks listed live per M tile.

    a:    (M, K)              — activations, M % block_m == K % block_k == 0.
    b:    (K, N)              — dense weights, N % block_n == 0.
    kidx: (m_tiles, max_cnt) int32 — live K-block ids per M tile (entries
          past cnt[i] are dead: any valid id, only DMA'd, never multiplied).
    cnt:  (m_tiles,) int32    — live blocks per M tile.
    """
    m, k = a.shape
    kb, n = b.shape
    assert k == kb, (k, kb)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    m_tiles = m // block_m
    max_cnt = kidx.shape[1]
    assert kidx.shape == (m_tiles, max_cnt), (kidx.shape, (m_tiles, max_cnt))
    grid = (m_tiles, n // block_n, max_cnt)
    flat_kidx = kidx.reshape(-1).astype(jnp.int32)
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        functools.partial(_sparse_a_kernel, nkc=max_cnt),
        name="sparse_a",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                # A tile selected by metadata: the AMUX on the A side.
                pl.BlockSpec(
                    (block_m, block_k),
                    lambda i, j, kc, kidx_s, cnt_s: (i, kidx_s[i * max_cnt + kc])),
                # dense B walked in compacted order via the same metadata.
                pl.BlockSpec(
                    (block_k, block_n),
                    lambda i, j, kc, kidx_s, cnt_s: (kidx_s[i * max_cnt + kc], j)),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n),
                lambda i, j, kc, kidx_s, cnt_s: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=checked_interpret(interpret),
    )(flat_kidx, cnt.astype(jnp.int32), a, b)
