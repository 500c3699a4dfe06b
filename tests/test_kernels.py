"""Pallas kernels vs pure-jnp oracles: shape/dtype/sparsity sweeps.

All kernels run in interpret mode (CPU) with the same BlockSpec logic that
targets TPU.  The hypothesis shape/sparsity sweep lives in
``tests/test_properties.py`` (guarded with ``pytest.importorskip`` —
hypothesis is an optional [test] dependency).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (GriffinWeights, balance_columns, dense_matmul,
                           griffin_matmul, preprocess_weights, stack_weights)
from repro.kernels.dense_gemm.ref import dense_matmul_ref
from repro.kernels.griffin_spmm.ops import decompact_weights
from repro.kernels.griffin_spmm.ref import griffin_spmm_ref
from repro.sparsity import (block_prune, magnitude_prune, sparsify_params,
                            sparsity_of)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 16, 8), (48, 96, 80), (33, 70, 17),
                                   (128, 256, 128)])
def test_dense_matmul_matches_ref(dtype, shape):
    m, k, n = shape
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(m, k), dtype=dtype)
    b = jnp.asarray(rng.randn(k, n), dtype=dtype)
    out = dense_matmul(a, b, interpret=True)
    ref = dense_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("balance", [False, True])
@pytest.mark.parametrize("dual", [False, True])
def test_griffin_spmm_matches_ref(dtype, balance, dual):
    rng = np.random.RandomState(1)
    m, k, n = 32, 128, 96
    w = jnp.asarray(rng.randn(k, n), dtype=jnp.float32)
    w = block_prune(w, 0.6, block_k=16, unit=8).astype(dtype)
    gw = preprocess_weights(np.asarray(w.astype(jnp.float32)), block_k=16,
                            block_n=32, unit=8, balance=balance)
    gw.b_comp = gw.b_comp.astype(dtype)
    a = jnp.asarray(rng.randn(m, k), dtype=dtype)
    out = griffin_matmul(a, gw, dual=dual, interpret=True)
    ref = griffin_spmm_ref(a, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_dual_skips_zero_a_blocks_exactly():
    """Dual mode must be bit-identical: skipped A blocks are exact zeros."""
    rng = np.random.RandomState(2)
    a = rng.randn(16, 64).astype(np.float32)
    a[:, 16:48] = 0                       # two all-zero K blocks
    w = block_prune(jnp.asarray(rng.randn(64, 32).astype(np.float32)),
                    0.5, block_k=16, unit=8)
    gw = preprocess_weights(np.asarray(w), block_k=16, block_n=16, unit=8,
                            balance=False)
    out_b = griffin_matmul(jnp.asarray(a), gw, dual=False, interpret=True)
    out_ab = griffin_matmul(jnp.asarray(a), gw, dual=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_ab))


def test_dual_zero_test_bf16_matches_single_mode():
    """The dual kernel's zero test widens each bf16 A tile to f32 before
    comparing (Mosaic cannot reduce a bf16 compare's packed i1 mask).  In
    bf16 the predicate must still skip exactly the all-zero tiles — here
    one all-zero K tile, one live tile, one tile of -0.0 (zero) and one
    whose only nonzero is the smallest normal bf16 (live) — and the
    output must be bit-equal to the single-mode kernel."""
    rng = np.random.RandomState(5)
    k = 4 * 128
    a = rng.randn(16, k).astype(np.float32)
    a[:, 0:128] = 0.0                          # all-zero tile
    a[:, 256:384] = -0.0                       # signed zeros: still zero
    a[:, 384:512] = 0.0
    # row 3 lives only through the barely-live tile, so skipping that tile
    # would zero the row
    a[3, :] = 0.0
    a[3, 400] = float(jnp.finfo(jnp.bfloat16).tiny)
    a = jnp.asarray(a, jnp.bfloat16)
    w = block_prune(jnp.asarray(rng.randn(k, 256), jnp.bfloat16), 0.5,
                    block_k=128, unit=32)
    gw = preprocess_weights(np.asarray(w), block_k=128, block_n=128,
                            unit=32, balance=False)
    out_b = griffin_matmul(a, gw, dual=False, interpret=True)
    out_ab = griffin_matmul(a, gw, dual=True, interpret=True)
    assert out_ab.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out_ab.astype(jnp.float32)),
                                  np.asarray(out_b.astype(jnp.float32)))
    assert np.asarray(out_ab[3].astype(jnp.float32)).any()
    ref = np.asarray(a, np.float32) @ np.asarray(w, np.float32)
    np.testing.assert_allclose(np.asarray(out_ab, np.float32), ref,
                               rtol=2e-2, atol=2e-2)


def test_balancing_reduces_grid_depth_on_clustered_patterns():
    """Channel-clustered pruning (the realistic case, cf. MaskModel) gives
    the shuffle analogue something to balance."""
    rng = np.random.RandomState(3)
    k, n, bk, bn, unit = 256, 256, 16, 64, 16
    # half the unit-columns share pattern P1, half share P2
    p1 = rng.rand(k // bk) < 0.3
    p2 = rng.rand(k // bk) < 0.3
    w = np.zeros((k, n), np.float32)
    for u in range(n // unit):
        pat = p1 if u % 2 == 0 else p2
        for kb in range(k // bk):
            if pat[kb]:
                w[kb * bk:(kb + 1) * bk, u * unit:(u + 1) * unit] = \
                    rng.randn(bk, unit)
    gw_off = preprocess_weights(w, block_k=bk, block_n=bn, unit=unit,
                                balance=False)
    gw_on = preprocess_weights(w, block_k=bk, block_n=bn, unit=unit,
                               balance=True)
    assert gw_on.kidx.shape[1] <= gw_off.kidx.shape[1]
    a = rng.randn(8, k).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(griffin_matmul(jnp.asarray(a), gw_on, interpret=True)),
        a @ w, rtol=2e-4, atol=2e-4)


def test_pruning_hits_target_sparsity():
    rng = np.random.RandomState(4)
    w = jnp.asarray(rng.randn(128, 96).astype(np.float32))
    assert abs(float(sparsity_of(magnitude_prune(w, 0.8))) - 0.8) < 0.02
    wb = block_prune(w, 0.75, block_k=32, unit=16)
    assert 0.6 < float(sparsity_of(wb)) < 0.9


def _gemm_params(seed):
    rng = np.random.default_rng(seed)
    return {"layers": [
        {"wo": rng.standard_normal((64, 64)).astype(np.float32),
         "w_up": rng.standard_normal((64, 96)).astype(np.float32),
         "w_down": rng.standard_normal((96, 48)).astype(np.float32)}]}


def _griffin_leaves(tree):
    return [l for l in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, GriffinWeights))
        if isinstance(l, GriffinWeights)]


@pytest.mark.parametrize("bk", [16, 32, 64])
def test_sparsify_params_idempotent(bk):
    """Two ``sparsify_params`` calls on the same source weights give
    bit-identical compacted GriffinWeights, and the source is untouched:
    pruning and compaction hold no rng, cache or mutation."""
    params = _gemm_params(7)
    source = jax.tree.map(np.copy, params)
    kw = dict(block_k=bk, block_n=bk, unit=8)
    once = _griffin_leaves(sparsify_params(params, 0.7, **kw))
    twice = _griffin_leaves(sparsify_params(params, 0.7, **kw))
    assert len(once) == len(twice) == 3
    for a, b in zip(once, twice):
        assert (a.k, a.n, a.block_k, a.block_n) == \
            (b.k, b.n, b.block_k, b.block_n)
        assert a.block_k == bk
        for fa, fb in zip((a.b_comp, a.kidx, a.cnt, a.inv_perm),
                          (b.b_comp, b.kidx, b.cnt, b.inv_perm)):
            if fa is None or fb is None:
                assert fa is None and fb is None
            else:
                np.testing.assert_array_equal(np.asarray(fa),
                                              np.asarray(fb))
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(source)):
        np.testing.assert_array_equal(x, y)


def test_sparsify_block_sizes_reach_compacted_leaves():
    """``block_k`` / ``block_n`` become each compacted leaf's blocks,
    clamped to the leaf's own dims, and the compacted leaf decompacts to
    the pruned-dense twin of the same call."""
    params = _gemm_params(8)
    kw = dict(block_k=32, block_n=64, unit=8)
    compact = sparsify_params(params, 0.6, **kw)["layers"][0]
    dense = sparsify_params(params, 0.6, compact=False, **kw)["layers"][0]
    for name in ("wo", "w_up", "w_down"):
        gw = compact[name]
        k, n = params["layers"][0][name].shape
        assert (gw.block_k, gw.block_n) == (min(32, k), min(64, n)), name
        np.testing.assert_array_equal(
            np.asarray(decompact_weights(gw))[:k, :n],
            np.asarray(dense[name]))


# ---------------------------------------------------------------------------
# GriffinWeights container: stacking, slicing under jit, density memo
# ---------------------------------------------------------------------------

def _toy_gw(seed, k=64, n=64, density=0.4, bk=16, bn=32):
    rng = np.random.RandomState(seed)
    w = rng.randn(k, n).astype(np.float32)
    mask = rng.rand(k // bk, n // 8) < density
    w *= np.repeat(np.repeat(mask, bk, 0), 8, 1)
    return w, preprocess_weights(w, block_k=bk, block_n=bn, unit=8,
                                 balance=False)


def test_stack_weights_clamp_padding_and_parity():
    """Members with shallower grids pad kidx by clamp-repeating the last
    block id with zero data, so the padded tail multiplies by zeros —
    each stacked slice stays numerically identical to its source."""
    w0, g0 = _toy_gw(0, density=0.2)
    w1, g1 = _toy_gw(1, density=0.9)      # deeper grid: forces padding of g0
    assert g0.kidx.shape[-1] < g1.kidx.shape[-1]
    stacked = stack_weights([g0, g1])
    max_cnt = g1.kidx.shape[-1]
    assert stacked.kidx.shape == (2, g0.kidx.shape[0], max_cnt)
    assert stacked.b_comp.shape[1] == max_cnt * g0.block_k
    # clamp padding: dead kidx entries repeat the member's last id ...
    pad = np.asarray(stacked.kidx[0, :, g0.kidx.shape[-1]:])
    last = np.asarray(g0.kidx[:, -1])
    assert (pad == last[:, None]).all()
    # ... and the padded b_comp rows are exact zeros
    assert not np.asarray(
        stacked.b_comp[0, g0.b_comp.shape[0]:, :]).any()
    # cnt is NOT padded: the kernel walks only the live prefix
    np.testing.assert_array_equal(np.asarray(stacked.cnt[0]),
                                  np.asarray(g0.cnt))
    a = np.random.RandomState(7).randn(8, 64).astype(np.float32)
    for i, w in enumerate((w0, w1)):
        out = griffin_matmul(jnp.asarray(a), stacked[i], interpret=True)
        np.testing.assert_allclose(np.asarray(out), a @ w,
                                   rtol=2e-4, atol=2e-4)


def test_stacked_getitem_under_jit():
    """``gw[i]`` inside a jitted fn (traced index included) must slice
    every array leaf — the layout the model stacks' ``lax.scan`` and the
    MoE per-expert loop rely on."""
    w0, g0 = _toy_gw(2)
    w1, g1 = _toy_gw(3)
    stacked = stack_weights([g0, g1])
    a = jnp.asarray(np.random.RandomState(8).randn(8, 64).astype(np.float32))

    @jax.jit
    def run(a, gw, i):
        sl = gw[i]
        return sl.b_comp.sum(), sl.kidx.shape, sl.cnt

    for i, g in enumerate((g0, g1)):
        s, kshape, cnt = run(a, stacked, i)
        assert kshape[0] == g.kidx.shape[0]
        assert kshape[1] == stacked.kidx.shape[-1]
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(g.cnt))
        np.testing.assert_allclose(float(s), float(jnp.sum(g.b_comp)),
                                   rtol=1e-6)
    # concrete slicing composes with execution
    out = griffin_matmul(a, stacked[1], interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ w1,
                               rtol=2e-4, atol=2e-4)


def test_density_memoized_without_pytree_leakage():
    _, gw = _toy_gw(4)
    d = gw.density
    assert "_density_memo" in gw.__dict__ and gw.__dict__[
        "_density_memo"] == d
    assert gw.density == d                       # second read hits the memo
    # flatten/unflatten rebuilds from registered fields only: the copy must
    # not inherit the memo, and must recompute the same value lazily
    leaves, treedef = jax.tree.flatten(gw)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert "_density_memo" not in rebuilt.__dict__
    assert rebuilt.density == d
    # a tree-mapped copy with different cnt data recomputes, not inherits
    halved = jax.tree.unflatten(treedef, leaves)
    halved.cnt = halved.cnt // 2
    assert halved.density < d
