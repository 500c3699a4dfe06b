"""The benchmark's seeded Sparse.B weights: as many whole blocks as the
program's pruner keeps, in a pattern drawn from the configuration's
``mask_seed`` (the same for every run seed, uneven over the column tiles),
and left unchanged by the program's own pruning and compaction."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import weights  # noqa: E402

PRUNING = {"weight_sparsity": 0.8, "block_k": 16, "block_n": 32, "unit": 32,
           "mask_seed": 7, "pruned": ["wq"]}
CONF = {"init": {"norm_std": 0.1, "embed_std": 1.0}, "pruning": PRUNING}
SHAPE = (3, 256, 512)


def _make(seed, conf=CONF):
    shapes = {"wq": jax.ShapeDtypeStruct(SHAPE, np.float32)}
    return np.asarray(weights.make(shapes, conf, seed)["wq"])


def _blocks(w, bk=16, bn=32):
    *lead, k, n = w.shape
    return (w.reshape(*lead, k // bk, bk, n // bn, bn) != 0).any(axis=(-3,
                                                                       -1))


@pytest.mark.parametrize("seed", [1, 2 ** 32 + 3])
def test_kept_blocks_as_the_pruner_keeps_them(seed):
    plan = weights.block_plan(256, 512, PRUNING)
    # 16 x 16 blocks of 16 x 32: the pruner keeps round(51.2) = 51
    assert plan["kept"] == 51 and plan["nbn"] == 16
    per_tile = _blocks(_make(seed)).sum(axis=-2)
    assert (per_tile.sum(axis=-1) == 51).all()
    # a uniform choice leaves the column tiles uneven
    assert (per_tile.max(axis=-1) > per_tile.min(axis=-1) + 1).all()


def test_mask_seed_fixes_the_pattern_and_the_run_seed_the_values():
    a, b = _make(5), _make(6)
    assert np.array_equal(_blocks(a), _blocks(b))
    assert not np.allclose(a, b)
    shapes = {"wq": jax.ShapeDtypeStruct(SHAPE, np.float32)}
    assert np.array_equal(np.asarray(weights.masks(shapes, CONF)["wq"]),
                          _blocks(a))
    other = dict(CONF, pruning=dict(PRUNING, mask_seed=8))
    assert not np.array_equal(_blocks(_make(5, other)), _blocks(a))


def test_program_pruning_keeps_every_value_and_every_shape():
    from repro.kernels.griffin_spmm.ops import decompact_weights
    from repro.sparsity import sparsify_params
    compact = lambda w: sparsify_params({"wq": w}, 0.8, compact=True,
                                        block_k=16, block_n=32,
                                        unit=32)["wq"]
    w = _make(7)
    gw = compact(w)
    for i in range(w.shape[0]):
        np.testing.assert_array_equal(np.asarray(decompact_weights(gw[i])),
                                      w[i])
    assert jax.tree.map(np.shape, gw) == \
        jax.tree.map(np.shape, compact(_make(8)))
