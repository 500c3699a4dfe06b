"""Dense blocked GEMM Pallas kernel — the optimized dense baseline core.

This is the TPU counterpart of the paper's Section II-A dense architecture:
a tiled output-stationary matmul with explicit VMEM residency via BlockSpec.
Block shapes default to MXU-aligned 128 multiples (the (K0, N0, M0) unrolling
of Figure 1 maps onto the 128x128 systolic MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ...configs.platform import checked_interpret


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Grid (mt, nt, kt): accumulate A[i,k] @ B[k,j] into a VMEM f32 scratch,
    flushing to the output block on the last k step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def dense_matmul_kernel(a: jax.Array, b: jax.Array, *, block_m: int,
                        block_n: int, block_k: int, out_dtype=None,
                        interpret: bool = False) -> jax.Array:
    """C = A @ B with (block_m, block_k) x (block_k, block_n) VMEM tiles.

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    nk = k // block_k
    out_dtype = out_dtype or a.dtype
    grid = (m // block_m, n // block_n, nk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        name="dense_gemm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=checked_interpret(interpret),
    )(a, b)
