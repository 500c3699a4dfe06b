"""Shard-parity tier: the real Pallas kernels under SPMD via shard_map
(DESIGN.md Section 10).

The serving layout never splits a GEMM contraction dim, so each device's
share of every matmul is fully local and the kernels run under
``jax.shard_map`` with zero in-kernel collectives.  Two
tiers, mirroring tests/test_mesh_serve.py:

  - tier-1 (unmarked, runs on one device): the *decomposition laws* the
    shard_map paths rely on — running a shard-local kernel entry
    (``griffin_matmul_shard`` / ``sparse_a_matmul_shard`` /
    ``dense_matmul_shard``) on each manually-cut N-slice and
    concatenating must be bit-equal to the unsharded kernel — plus the
    shard-spec/shardability predicates and the 1x1-mesh degenerate case.

  - mesh-marked (skip below 8 devices, run by the CI ``sharded`` job and
    any ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` tier-1
    invocation): the shard_map'd ops on real {1x2, 2x2, 2x4} meshes must
    be bit-equal to the unsharded kernels and allclose to the
    decompaction oracle, and ``griffin_linear`` under a ``spmd_mesh``
    scope must take the shard_map path (KERNEL_DISPATCH counter) for all
    four execution Modes — with ``spmd_kernels=False`` retiring it to
    the oracle.

Bitwise (not allclose) kernel parity holds because a shard runs the same
per-tile fp32 accumulation as the unsharded kernel over the same K
blocks in the same order; only the oracle (a plain jnp dot over the
decompacted matrix) reduces in a different order.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec import Mode
from repro.kernels.dense_gemm import ops as dense_ops
from repro.kernels.griffin_spmm import ops as spmm_ops
from repro.kernels.sparse_a import ops as sparse_a_ops
from repro.models.common import (griffin_linear, kernel_dispatch_counts,
                                 reset_kernel_dispatch, sparse_execution)
from repro.runtime.sharding import (gemm_shard_specs, kernel_shardable,
                                    spmm_shard_specs)
from repro.sparsity.pruning import block_prune

BLK = dict(block_k=16, block_n=16, unit=8)      # reduced-config granularity


def _needs_devices(n: int):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs {n} devices (export XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")


def _mesh(spec: str):
    from repro.launch.mesh import serve_mesh
    return serve_mesh(spec)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal(shape).astype(np.float32))


def _sparse_rows(shape, seed=1):
    """Activations with whole zero K-blocks (the Sparse.A workload)."""
    a = np.asarray(_rand(shape, seed)).copy()
    a[:, shape[1] // 4: 3 * shape[1] // 4] = 0.0
    return jnp.asarray(a)


def _gw(k=64, n=128, seed=2, balance=True):
    w = block_prune(_rand((k, n), seed), 0.6, BLK["block_k"], BLK["unit"])
    return spmm_ops.preprocess_weights(np.asarray(w), balance=balance, **BLK)


# ---------------------------------------------------------------------------
# tier-1: specs and shardability predicates
# ---------------------------------------------------------------------------

def test_shard_spec_reexports_are_the_kernel_specs():
    """runtime.sharding's view of the per-shard operand layout must be the
    kernel packages' own definition — one source of truth for dispatch,
    layout rules and tests."""
    assert spmm_shard_specs() == spmm_ops.shard_specs()
    assert gemm_shard_specs() == sparse_a_ops.shard_specs()
    from jax.sharding import PartitionSpec as P
    in_specs, out_spec = spmm_ops.shard_specs("model")
    # activations replicated; b_comp split on padded-N; kidx/cnt on the
    # N-tile axis; output on N
    assert in_specs == (P(), P(None, "model"), P("model", None), P("model"))
    assert out_spec == P(None, "model")
    in_specs, out_spec = sparse_a_ops.shard_specs("model")
    # per-M-tile runtime metadata replicates — an output split never
    # touches which A blocks are live
    assert in_specs == (P(), P(None, "model"), P(), P())
    assert out_spec == P(None, "model")


def test_shardable_predicates():
    gw = _gw(n=128)                              # 8 N tiles of 16
    assert spmm_ops.shardable(gw, 1)
    assert spmm_ops.shardable(gw, 2)
    assert spmm_ops.shardable(gw, 4)
    assert not spmm_ops.shardable(gw, 3)         # tiles must split evenly
    stacked = spmm_ops.stack_weights([gw, _gw(n=128, seed=3)])
    assert not spmm_ops.shardable(stacked, 2)    # engine slices per layer
    w = _rand((64, 96))
    for ops in (dense_ops, sparse_a_ops):
        assert ops.shardable(w, 2) and ops.shardable(w, 4)
        assert not ops.shardable(w, 5)           # 96 % 5 != 0
        assert not ops.shardable(jnp.stack([w, w]), 2)


def test_kernel_shardable_leaf_predicate():
    """The layout-rule wrapper applies the right per-representation
    predicate and refuses meshes without the model axis."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class SpecMesh:
        shape: dict
        axis_names: tuple

    m22 = SpecMesh({"data": 2, "model": 2}, ("data", "model"))
    assert kernel_shardable(_gw(n=128), m22)
    assert kernel_shardable(_rand((64, 64)), m22)
    assert not kernel_shardable(_rand((64, 65)), m22)
    bad = SpecMesh({"x": 2}, ("x",))
    assert not kernel_shardable(_rand((64, 64)), bad)


# ---------------------------------------------------------------------------
# tier-1: decomposition laws (single device — manual N-slices)
# ---------------------------------------------------------------------------

def test_dense_shard_decomposition_law():
    """Concatenated per-shard dense kernels == the unsharded kernel tiled
    at the shard's block_n, bitwise.  A shard's local N clamps its block_n
    below the global default; the reference runs at that same tiling,
    because XLA's CPU backend does not promise bit-equal dots across
    different tile widths (allclose covers that case)."""
    a, w = _rand((8, 64)), _rand((64, 64), seed=4)
    for shards in (2, 4):
        n_loc = w.shape[1] // shards
        parts = [dense_ops.dense_matmul_shard(
                     a, w[:, s * n_loc:(s + 1) * n_loc],
                     block_m=128, block_n=128, block_k=128, interpret=True)
                 for s in range(shards)]
        got = np.asarray(jnp.concatenate(parts, 1))
        ref = dense_ops.dense_matmul(a, w, block_n=n_loc, interpret=True)
        np.testing.assert_array_equal(got, np.asarray(ref))
        np.testing.assert_allclose(
            got, np.asarray(dense_ops.dense_matmul(a, w, interpret=True)),
            rtol=1e-5, atol=1e-5)


def test_sparse_a_shard_decomposition_law():
    """Per-shard sparse_a kernels under one shared (replicated) metadata
    == the unsharded kernel at the shard's block_n, bitwise: the M-tile
    compaction is invariant to the output split."""
    a, w = _sparse_rows((8, 64)), _rand((64, 64), seed=5)
    meta = sparse_a_ops.compact_activations(a, block_m=128, block_k=128)
    for shards in (2, 4):
        n_loc = w.shape[1] // shards
        parts = [sparse_a_ops.sparse_a_matmul_shard(
                     a, w[:, s * n_loc:(s + 1) * n_loc], meta.kidx, meta.cnt,
                     block_m=meta.block_m, block_k=meta.block_k,
                     block_n=128, interpret=True)
                 for s in range(shards)]
        got = np.asarray(jnp.concatenate(parts, 1))
        # equal tiling: bitwise; the default (wider) tiling: allclose
        ref = sparse_a_ops.sparse_a_matmul(a, w, block_n=n_loc,
                                           interpret=True)
        np.testing.assert_array_equal(got, np.asarray(ref))
        np.testing.assert_allclose(
            got, np.asarray(sparse_a_ops.sparse_a_matmul(a, w,
                                                         interpret=True)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dual", [False, True], ids=["B", "AB"])
@pytest.mark.parametrize("balance", [False, True],
                         ids=["plain", "balanced"])
def test_griffin_shard_decomposition_law(dual, balance):
    """A contiguous group of N tiles with its own metadata rows is a
    complete kernel problem: per-shard ``griffin_matmul_shard`` calls on
    manual slices, concatenated and globally un-permuted/unpadded, must
    be bit-equal to the unsharded kernel and allclose to the decompaction
    oracle."""
    gw = _gw(n=120, balance=balance)             # unpad [:, :n] is real
    a = _sparse_rows((8, 64)) if dual else _rand((8, 64), seed=6)
    ref = spmm_ops.griffin_matmul(a, gw, dual=dual, interpret=True)
    nt, bn = gw.kidx.shape[0], gw.block_n
    for shards in (2, 4):
        assert spmm_ops.shardable(gw, shards)
        tps = nt // shards
        parts = []
        for s in range(shards):
            sl = slice(s * tps, (s + 1) * tps)
            parts.append(spmm_ops.griffin_matmul_shard(
                a, gw.b_comp[:, s * tps * bn:(s + 1) * tps * bn],
                gw.kidx[sl], gw.cnt[sl], block_m=8, block_k=gw.block_k,
                block_n=bn, dual=dual, interpret=True))
        out = jnp.concatenate(parts, axis=1)
        if gw.inv_perm is not None:              # global column ops stay
            out = out[:, gw.inv_perm]            # with the caller
        out = out[:, :gw.n]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # the oracle ignores A-block predication, but skipped A blocks are
    # exactly zero, so the values agree for the dual mode too
    oracle = jnp.dot(a, spmm_ops.decompact_weights(gw),
                     preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oracle),
                               atol=1e-5)


def test_shard_map_1x1_mesh_is_identity():
    """mesh.size == 1: the shard_map path must reproduce the unsharded
    kernel bitwise (the degenerate cell of the parity matrix) — runnable
    on a single device."""
    mesh = _mesh("1x1")
    a, w = _rand((8, 64)), _rand((64, 64), seed=7)
    np.testing.assert_array_equal(
        np.asarray(dense_ops.dense_matmul(a, w, interpret=True, mesh=mesh)),
        np.asarray(dense_ops.dense_matmul(a, w, interpret=True)))
    sa = _sparse_rows((8, 64))
    np.testing.assert_array_equal(
        np.asarray(sparse_a_ops.sparse_a_matmul(sa, w, interpret=True,
                                                mesh=mesh)),
        np.asarray(sparse_a_ops.sparse_a_matmul(sa, w, interpret=True)))
    gw = _gw()
    np.testing.assert_array_equal(
        np.asarray(spmm_ops.griffin_matmul(a, gw, interpret=True,
                                           mesh=mesh)),
        np.asarray(spmm_ops.griffin_matmul(a, gw, interpret=True)))


# ---------------------------------------------------------------------------
# mesh-marked: real shard_map on emulated multi-device meshes
# ---------------------------------------------------------------------------

MESHES = ["1x2", "2x2", "2x4"]


@pytest.mark.mesh
@_needs_devices(8)
@pytest.mark.parametrize("spec", MESHES)
@pytest.mark.parametrize("dual", [False, True], ids=["B", "AB"])
def test_griffin_shard_map_parity(spec, dual):
    mesh = _mesh(spec)
    gw = _gw(n=128)
    a = _sparse_rows((8, 64)) if dual else _rand((8, 64), seed=8)
    ref = spmm_ops.griffin_matmul(a, gw, dual=dual, interpret=True)
    got = spmm_ops.griffin_matmul(a, gw, dual=dual, interpret=True,
                                  mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    oracle = spmm_ops.griffin_matmul(a, gw, dual=dual, spmd=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               atol=1e-5)


@pytest.mark.mesh
@_needs_devices(8)
@pytest.mark.parametrize("spec", MESHES)
def test_dense_and_sparse_a_shard_map_parity(spec):
    """shard_map'd dense / sparse_a kernels == the unsharded kernel at the
    shard's tile width (bitwise), allclose to the default tiling: XLA's
    CPU backend does not promise bit-equal dots across tile widths."""
    mesh = _mesh(spec)
    w = _rand((64, 64), seed=9)
    a, sa = _rand((8, 64), seed=10), _sparse_rows((8, 64))
    bn = w.shape[1] // mesh.shape["model"]
    got = dense_ops.dense_matmul(a, w, interpret=True, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(dense_ops.dense_matmul(a, w, block_n=bn, interpret=True)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense_ops.dense_matmul(a, w,
                                                           interpret=True)),
        rtol=1e-5, atol=1e-5)
    got = sparse_a_ops.sparse_a_matmul(sa, w, interpret=True, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(sparse_a_ops.sparse_a_matmul(sa, w, block_n=bn,
                                                interpret=True)))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(sparse_a_ops.sparse_a_matmul(sa, w, spmd=True)),
        atol=1e-5)


def _linear_case(mode):
    """(x, w, a_sparsity) driving griffin_linear into ``mode``."""
    if mode in (Mode.B, Mode.AB):
        w = _gw(n=128)
    else:
        w = _rand((64, 128), seed=11)
    sparse_a = mode in (Mode.A, Mode.AB)
    x = _sparse_rows((8, 64)) if sparse_a else _rand((8, 64), seed=12)
    return x, w, (0.9 if sparse_a else 0.0)


@pytest.mark.mesh
@_needs_devices(8)
@pytest.mark.parametrize("mode", list(Mode), ids=[m.value for m in Mode])
def test_griffin_linear_shard_map_all_modes_2x4(mode):
    """Every execution Mode's GEMM goes through the shard_map'd real
    kernel (dispatch counter), bit-equal to the single-device kernel."""
    mesh = _mesh("2x4")
    x, w, a_sp = _linear_case(mode)
    with sparse_execution(use_kernels=True, interpret=True, a_sparsity=a_sp):
        ref = griffin_linear(x, w)
    reset_kernel_dispatch()
    with sparse_execution(use_kernels=True, interpret=True, a_sparsity=a_sp,
                          spmd_mesh=mesh):
        got = griffin_linear(x, w)
    counts = kernel_dispatch_counts()
    assert counts.get("shard_map", 0) == 1 and \
        counts.get("spmd_oracle", 0) == 0, (mode, counts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.mesh
@_needs_devices(8)
def test_griffin_linear_spmd_kernels_false_forces_oracle():
    """spmd_kernels=False retires the shard_map path: the decompaction
    oracle serves the GEMM (allclose, different reduction order) and the
    dispatch counter proves which path ran."""
    mesh = _mesh("2x4")
    x, gw = _rand((8, 64), seed=13), _gw(n=128)
    with sparse_execution(use_kernels=True, interpret=True):
        ref = griffin_linear(x, gw)
    reset_kernel_dispatch()
    with sparse_execution(use_kernels=True, spmd_mesh=mesh,
                          spmd_kernels=False):
        got = griffin_linear(x, gw)
    counts = kernel_dispatch_counts()
    assert counts.get("spmd_oracle", 0) == 1 and \
        counts.get("shard_map", 0) == 0, counts
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.mesh
@_needs_devices(8)
def test_griffin_linear_unshardable_leaf_falls_back_to_oracle():
    """A weight leaf whose N tiles do not divide the model axis cannot
    shard_map; dispatch falls back to the oracle instead of asserting."""
    mesh = _mesh("2x4")                          # mp = 4
    gw = _gw(n=48)                               # 3 N tiles: 3 % 4 != 0
    assert not spmm_ops.shardable(gw, 4)
    x = _rand((8, 64), seed=14)
    with pytest.raises(AssertionError):          # the op itself refuses
        spmm_ops.griffin_matmul(x, gw, interpret=True, mesh=mesh)
    reset_kernel_dispatch()
    with sparse_execution(use_kernels=True, spmd_mesh=mesh):
        got = griffin_linear(x, gw)
    assert kernel_dispatch_counts().get("spmd_oracle", 0) == 1
    with sparse_execution(use_kernels=True, interpret=True):
        ref = griffin_linear(x, gw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
