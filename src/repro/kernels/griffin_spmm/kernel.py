"""Griffin block-sparse GEMM Pallas kernel (the paper's technique on TPU).

TPU adaptation of the paper's mechanisms (DESIGN.md Section 3):

  - **B preprocessing** (Sparse.B): the weight matrix is compacted offline —
    all-zero (block_k x block_n) blocks are dropped, and per output tile j a
    metadata list ``kidx[j]`` of surviving K-block ids plus a count ``cnt[j]``
    is carried as *scalar-prefetch* operands.  The kernel walks the compacted
    list; the data-dependent ``BlockSpec index_map`` plays the role of the
    paper's AMUX (metadata selects which A tile each multiply consumes).
  - **On-the-fly A skipping** (Sparse.A / dual): with ``dual=True`` the
    kernel tests the fetched A tile for all-zero and predicates the MXU op
    (``pl.when``), the block-granular analogue of the paper's zero-mask +
    arbitration steps (Fig. 3 steps 2-4).
  - **Load balancing** (shuffle): ops.py can permute output columns so each
    N tile receives a balanced number of surviving blocks, shrinking the
    padded grid depth max_j cnt[j] — the paper's rotation shuffler at tile
    granularity.

Grid: (m_tiles, n_tiles, max_cnt); the k axis is the *compacted* position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ...configs.platform import checked_interpret


def _spmm_kernel(kidx_ref, cnt_ref, a_ref, b_ref, o_ref, acc_ref,
                 *, nkc: int, dual: bool):
    kc = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = kc < cnt_ref[j]
    if dual:
        # Dual sparsity: also skip when the (dynamic) A tile is all-zero —
        # the paper's on-the-fly zero detection at block granularity.  The
        # test runs on an f32 copy: Mosaic cannot reduce the packed i1 mask
        # a bf16 compare produces, and widening keeps every value (and so
        # the predicate) exact.
        a_blk = a_ref[...]
        live = jnp.logical_and(
            live, jnp.any(a_blk.astype(jnp.float32) != 0))

        @pl.when(live)
        def _acc_dual():
            acc_ref[...] += jnp.dot(a_blk, b_ref[...],
                                    preferred_element_type=jnp.float32)
    else:
        @pl.when(live)
        def _acc():
            acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                    preferred_element_type=jnp.float32)

    @pl.when(kc == nkc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def griffin_spmm_kernel(a: jax.Array, b_comp: jax.Array, kidx: jax.Array,
                        cnt: jax.Array, *, block_m: int, block_k: int,
                        block_n: int, dual: bool = False, out_dtype=None,
                        interpret: bool = False) -> jax.Array:
    """C = A @ B from the block-compacted weight representation.

    a:      (M, K)            — activations, M % block_m == K % block_k == 0.
    b_comp: (max_cnt*block_k, N) — compacted weight blocks per N tile:
            rows [kc*block_k:(kc+1)*block_k] of column tile j hold the
            kidx[j, kc]-th K-block of the original (pruned) weights.
    kidx:   (n_tiles, max_cnt) int32 — source K-block ids (clamped padding).
    cnt:    (n_tiles,) int32  — surviving blocks per N tile.
    """
    m, k = a.shape
    kc_rows, n = b_comp.shape
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    n_tiles = n // block_n
    max_cnt = kc_rows // block_k
    assert kidx.shape == (n_tiles, max_cnt), (kidx.shape, (n_tiles, max_cnt))
    grid = (m // block_m, n_tiles, max_cnt)
    flat_kidx = kidx.reshape(-1).astype(jnp.int32)
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        functools.partial(_spmm_kernel, nkc=max_cnt, dual=dual),
        name="griffin_spmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                # A tile selected by metadata: the AMUX.
                pl.BlockSpec(
                    (block_m, block_k),
                    lambda i, j, kc, kidx_s, cnt_s: (i, kidx_s[j * max_cnt + kc])),
                # compacted B tile: walk the compressed stream.
                pl.BlockSpec(
                    (block_k, block_n),
                    lambda i, j, kc, kidx_s, cnt_s: (kc, j)),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n),
                lambda i, j, kc, kidx_s, cnt_s: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=checked_interpret(interpret),
    )(flat_kidx, cnt.astype(jnp.int32), a, b_comp)
