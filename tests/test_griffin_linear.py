"""The mode-dispatched execution substrate: select_mode edges, the
GriffinWeights pytree invariants, auto_matmul four-mode dispatch,
griffin_linear model wiring, and sharding of the compacted pytree."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.hybrid import SPARSE_THRESHOLD, select_mode
from repro.core.spec import Mode
from repro.kernels import (GriffinWeights, auto_matmul, preprocess_weights,
                           stack_weights)
from repro.models.common import griffin_linear, sparse_execution
from repro.runtime.sharding import shard_params
from repro.sparsity import block_prune, sparsify_params


# ---------------------------------------------------------------------------
# select_mode threshold edges
# ---------------------------------------------------------------------------

def test_select_mode_threshold_edges():
    t = SPARSE_THRESHOLD
    assert select_mode(0.0, 0.0) == Mode.DENSE
    # the threshold itself is NOT sparse (strictly-greater comparison)
    assert select_mode(t, t) == Mode.DENSE
    eps = 1e-9
    assert select_mode(t + eps, 0.0) == Mode.A
    assert select_mode(0.0, t + eps) == Mode.B
    assert select_mode(t + eps, t + eps) == Mode.AB
    assert select_mode(1.0, 1.0) == Mode.AB
    # custom threshold moves the edge
    assert select_mode(0.3, 0.0, threshold=0.5) == Mode.DENSE
    assert select_mode(0.6, 0.0, threshold=0.5) == Mode.A


# ---------------------------------------------------------------------------
# GriffinWeights invariants
# ---------------------------------------------------------------------------

def _gw(rng, k=64, n=64, sparsity=0.5, balance=False):
    w = block_prune(jnp.asarray(rng.randn(k, n), jnp.float32), sparsity,
                    block_k=16, unit=8)
    return w, preprocess_weights(np.asarray(w), block_k=16, block_n=16,
                                 unit=8, balance=balance)


def test_density_and_compaction_invariants():
    rng = np.random.RandomState(0)
    w, gw = _gw(rng, sparsity=0.5)
    # density = surviving block fraction; compaction = padded depth fraction
    assert 0.0 < gw.density <= 1.0
    assert gw.density <= gw.compaction <= 1.0   # padding to max_cnt >= mean
    _, gw_dense = _gw(rng, sparsity=0.0)
    assert gw_dense.density == gw_dense.compaction == 1.0
    z = preprocess_weights(np.zeros((64, 64), np.float32), block_k=16,
                           block_n=16, unit=8)
    assert z.density == 0.0
    assert z.kidx.shape[-1] == 1                 # minimal padded depth


def test_griffin_weights_is_a_pytree():
    rng = np.random.RandomState(1)
    _, gw = _gw(rng, balance=True)
    leaves = jax.tree.leaves(gw)
    assert len(leaves) == 4                      # b_comp, kidx, cnt, inv_perm
    gw2 = jax.tree.map(lambda a: a, gw)
    assert isinstance(gw2, GriffinWeights)
    assert (gw2.k, gw2.n, gw2.block_k, gw2.block_n) == \
        (gw.k, gw.n, gw.block_k, gw.block_n)     # static aux survives


def test_stack_weights_pads_to_common_depth_and_slices_back():
    rng = np.random.RandomState(2)
    ws, gws = zip(*[_gw(rng, sparsity=s) for s in (0.3, 0.7)])
    stacked = stack_weights(list(gws))
    assert stacked.kidx.shape == (2,) + (gws[0].kidx.shape[0],
                                         max(g.kidx.shape[-1] for g in gws))
    for i, (w, g) in enumerate(zip(ws, gws)):
        sl = stacked[i]                          # __getitem__ slices leaves
        assert isinstance(sl, GriffinWeights)
        x = jnp.asarray(rng.randn(8, 64), jnp.float32)
        with sparse_execution(interpret=True):
            out = griffin_linear(x, sl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# auto_matmul: all four modes dispatch and agree with the jnp product
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a_sp,b_sp,mode", [
    (0.0, 0.0, Mode.DENSE), (0.5, 0.0, Mode.A),
    (0.0, 0.5, Mode.B), (0.5, 0.5, Mode.AB)])
def test_auto_matmul_dispatches_every_mode(a_sp, b_sp, mode):
    rng = np.random.RandomState(3)
    w, gw = _gw(rng, sparsity=0.5)
    a = rng.randn(16, 64).astype(np.float32)
    a[:, :32] = 0                                # genuinely sparse A blocks
    a = jnp.asarray(a)
    assert select_mode(a_sp, b_sp) == mode
    out = auto_matmul(a, w, gw, a_sparsity=a_sp, b_sparsity=b_sp,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ w),
                               rtol=1e-4, atol=1e-4)


def test_auto_matmul_sparse_b_declared_without_preprocessing_falls_back():
    rng = np.random.RandomState(4)
    a = jnp.asarray(rng.randn(8, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16), jnp.float32)
    out = auto_matmul(a, w, None, b_sparsity=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ w),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# griffin_linear wiring + sharding of the compacted pytree
# ---------------------------------------------------------------------------

def test_griffin_linear_modes_match_plain_matmul():
    rng = np.random.RandomState(5)
    w, gw = _gw(rng, sparsity=0.6)
    x = jnp.asarray(rng.randn(2, 8, 64), jnp.float32)   # leading batch dims
    # default scope: plain jnp
    np.testing.assert_array_equal(np.asarray(griffin_linear(x, w)),
                                  np.asarray(x @ w))
    # kernel scope: dense + Sparse.A kernels; compacted weights: Sparse.B/dual
    for scope in (dict(), dict(a_sparsity=0.5)):
        with sparse_execution(interpret=True, **scope):
            np.testing.assert_allclose(
                np.asarray(griffin_linear(x, w)), np.asarray(x @ w),
                rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(griffin_linear(x, gw)), np.asarray(x @ w),
                rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("a_sp,dual", [(SPARSE_THRESHOLD - 1e-3, False),
                                       (SPARSE_THRESHOLD + 1e-3, True)],
                         ids=["below", "above"])
def test_griffin_linear_dual_only_above_threshold(monkeypatch, a_sp, dual):
    """On compacted weights a scope's declared A sparsity just below
    ``SPARSE_THRESHOLD`` keeps the Sparse.B kernel and just above it
    turns on dual predication; the product is the same either way."""
    import repro.models.common as common
    seen = []
    real = common.griffin_matmul

    def spy(*args, **kw):
        seen.append(kw["dual"])
        return real(*args, **kw)

    monkeypatch.setattr(common, "griffin_matmul", spy)
    rng = np.random.RandomState(7)
    w, gw = _gw(rng, sparsity=0.6)
    x = jnp.asarray(rng.randn(8, 64), jnp.float32)
    with sparse_execution(interpret=True, a_sparsity=a_sp):
        out = griffin_linear(x, gw)
    assert seen == [dual]
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)


def test_sparsify_params_and_sharding_of_compacted_tree():
    rng = np.random.RandomState(6)
    params = {"layers": {
        "wq": jnp.asarray(rng.randn(2, 64, 64), jnp.float32),   # stacked
        "w_down": jnp.asarray(rng.randn(64, 64), jnp.float32),
        "ln1": jnp.zeros((64,), jnp.float32),
        "wi": jnp.asarray(rng.randn(64, 4), jnp.float32),       # tiny: kept
    }}
    sp = sparsify_params(params, 0.5, block_k=16, block_n=16, unit=8)
    assert isinstance(sp["layers"]["wq"], GriffinWeights)
    assert sp["layers"]["wq"].b_comp.ndim == 3          # stacked leading L
    assert isinstance(sp["layers"]["w_down"], GriffinWeights)
    assert not isinstance(sp["layers"]["wi"], GriffinWeights)   # min_dim
    # dense twin carries the same values as the compacted representation
    dense_tw = sparsify_params(params, 0.5, block_k=16, block_n=16, unit=8,
                               compact=False)
    x = jnp.asarray(rng.randn(4, 64), jnp.float32)
    with sparse_execution(interpret=True):
        out = griffin_linear(x, sp["layers"]["w_down"])
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(x @ dense_tw["layers"]["w_down"]), rtol=1e-4, atol=1e-4)

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sh = shard_params(jax.eval_shape(lambda: sp), mesh)
    specs = {jax.tree_util.keystr(p): s.spec for p, s in
             jax.tree_util.tree_flatten_with_path(sh)[0]}
    # metadata replicated; b_comp shards only its output axis
    assert specs["['layers']['wq'].kidx"] == P(None, None, None)
    assert specs["['layers']['wq'].cnt"] == P(None, None)
    assert specs["['layers']['wq'].b_comp"][-1] in ("model", None)
    assert specs["['layers']['wq'].b_comp"][:-1] == (None, None)
