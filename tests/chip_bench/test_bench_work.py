"""The benchmark's operation and byte counts against hand counts."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import common, work  # noqa: E402

SPARSE = {"weight_sparsity": 0.8, "block_k": 128, "block_n": 128,
          "unit": 32, "pruned": ["wq", "head"]}


def test_stablelm_pruned_wq_by_hand():
    # 2048 x 2048 in 128 x 32 units: 1024 units, the pruner keeps
    # round(204.8) = 205 of them, which is 51 whole 128 x 128 blocks
    g = work.gemm("wq", 2048, 2048, rows=8, pruning=SPARSE, chips=1)
    assert g["params"] == 51 * 128 * 128 == 835584
    assert g["flops"] == 2 * 8 * 835584
    # kept blocks (bf16) + one int32 id per block and count per tile
    # + 8 activation rows + 8 output rows
    assert g["bytes"] == 835584 * 2 + (51 + 16) * 4 + 8 * 2048 * 2 \
        + 8 * 2048 * 2


def test_minitron_dense_gate_on_four_chips_by_hand():
    g = work.gemm("w_gate", 4096, 16384, rows=8, pruning=SPARSE, chips=4)
    assert g["params"] == 4096 * 16384
    assert g["flops"] == 2 * 8 * 4096 * 16384 / 4
    assert g["bytes"] == 4096 * 16384 * 2 / 4 + 8 * 4096 * 2 \
        + 8 * 16384 * 2 / 4


def test_decode_step_sums_the_layers():
    conf = common.load_json(common.BENCH / "configs" /
                            "stablelm-1.6b-b80.json")
    step = work.decode_step(conf)
    per = [work.gemm(n, k, m, 8, conf["pruning"], 1)["params"] * c
           for n, k, m, c in work.gemm_shapes(conf)]
    assert step["params"] == sum(per)
    # about a fifth of the 1.44 B weight-GEMM parameters survive
    dense = sum(k * m * c for _, k, m, c in work.gemm_shapes(conf))
    assert dense == 24 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 2048 * 100352
    assert 0.19 < step["params"] / dense < 0.2


def test_lower_bound_is_bandwidth_bound_at_decode():
    conf = common.load_json(common.BENCH / "configs" /
                            "stablelm-1.6b-b80.json")
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    step = work.decode_step(conf)
    t = work.lower_bound_s(conf, peak)
    assert t == pytest.approx(step["bytes"] / 819e9, rel=1e-9)
    assert t > step["flops"] / 197e12
