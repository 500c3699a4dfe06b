"""The benchmark's trace reduction, on events cut from TPU v5e traces of
the benchmark's runs (``data/trace_events.json``: the stablelm.b80.chat
slice cut to its first three decode chunks, their host spans, 81 op
events of the first and the second's four buffer allocations;
``data/trace_events_4chips.json``: one decode chunk
on a 1x4 mesh) and on small synthetic traces."""
import collections
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

EVENTS = json.loads((Path(__file__).parent / "data" /
                     "trace_events.json").read_text())


def _ops():
    return [e for e in EVENTS if e["kind"] == "op"]


def test_opcodes_of_recorded_events():
    codes = {trace.opcode(e["name"]) for e in _ops()}
    assert {"fusion", "copy", "custom-call", "while", "copy-start"} <= codes
    assert trace.opcode("%all-gather.3 = bf16[8,4096]{1,0} all-gather("
                        "bf16[8,1024]{1,0} %x), dimensions={1}") \
        == "all-gather"
    assert trace.opcode("jit_chunk_fn(123)") == "jit_chunk_fn(123)"


def test_kernels_told_by_their_operands():
    """The recorded kernels are told by their instruction's name, not by
    their operands: decode_attention leads with one int32 operand and
    bf16 ones, as no Sparse.B kernel does, and is still no dense_gemm."""
    calls = [e for e in _ops() if trace.opcode(e["name"]) == "custom-call"]
    told = collections.Counter(trace.kernel_of(e) for e in calls)
    assert told == {"griffin_spmm": 10, "decode_attention": 6, None: 4}
    assert all('custom_call_target="tpu_custom_call"' in e["name"]
               for e in calls if trace.kernel_of(e))
    assert all("AllocateBuffer" in e["name"]
               for e in calls if trace.kernel_of(e) is None)
    # a kernel that gave itself no name is booked under the instruction's
    # own name, never under another kernel's
    unnamed = {"name": '%_run.3 = bf16[8,4096]{1,0} custom-call('
                       'bf16[8,4096]{1,0} %a, bf16[4096,4096]{1,0} %b), '
                       'custom_call_target="tpu_custom_call"'}
    assert trace.kernel_of(unnamed) == "_run"


# op events of kernels that name themselves, as the program's pallas_calls
# do (``name=``); griffin_spmm and sparse_a lead with the same operands,
# and fused_rmsnorm stands for a kernel a later PR adds
NAMED = {
    "decode_attention": '%decode_attention.7 = (bf16[10,32,64]{2,1,0}, '
    'bf16[24,10,32,2048,64]{4,3,2,1,0}) custom-call(s32[96]{0} %plan, '
    'bf16[10,32,64]{2,1,0} %q), custom_call_target="tpu_custom_call"',
    "dense_gemm": '%dense_gemm.3 = bf16[8,256]{1,0} custom-call(bf16[8,4096]'
    '{1,0} %x, bf16[4096,256]{1,0} %w), custom_call_target="tpu_custom_call"',
    "sparse_a": '%sparse_a.5 = bf16[8,2048]{1,0} custom-call(s32[64]{0} %i, '
    's32[16]{0} %c, bf16[8,2048]{1,0} %x, bf16[2048,2048]{1,0} %w), '
    'custom_call_target="tpu_custom_call"',
    "griffin_spmm": '%griffin_spmm.12 = bf16[10,2048]{1,0} custom-call('
    's32[144]{0} %k, s32[16]{0} %c, bf16[10,2048]{1,0} %x, bf16[1152,2048]'
    '{1,0} %w), custom_call_target="tpu_custom_call"',
    "fused_rmsnorm": '%fused_rmsnorm.2 = f32[8,2048]{1,0} custom-call('
    'f32[8,2048]{1,0} %x), custom_call_target="tpu_custom_call"',
}


@pytest.mark.parametrize("kernel", sorted(NAMED))
def test_kernels_told_by_their_names(kernel):
    ev = _ev("/device:TPU:0", "op", NAMED[kernel], 100, 200)
    assert trace.kernel_of(ev) == kernel
    red = trace.reduce([_ev("host", "host", "bench.slice", 0, 1000),
                        _ev("/device:TPU:0", "module", "jit_chunk_fn(1)",
                            50, 400), ev])
    assert trace.kernel_sum(red, "chunk_fn", kernel) == pytest.approx(2e-7)
    [(label, sec)] = red["top_ops"]
    assert label == f"{kernel} @ jit_chunk_fn" and sec == pytest.approx(2e-7)


def test_reduction_of_recorded_slice():
    red = trace.reduce(EVENTS)
    sl = next(e for e in EVENTS if e["name"] == "bench.slice")
    lo, hi = sl["start_ns"], sl["start_ns"] + sl["dur_ns"]
    assert red["window_s"] == pytest.approx(sl["dur_ns"] * 1e-9)
    assert red["devices"] == 1
    # busy: union of the op intervals, async starts left out
    iv = sorted((max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi))
                for e in _ops()
                if not trace.opcode(e["name"]).endswith("-start"))
    busy, end = 0.0, -1.0
    for s, e in iv:
        if e <= max(s, end):
            continue
        busy += e - max(s, end)
        end = e
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    # kernel seconds, per decode-chunk program
    spmm = sum(e["dur_ns"] for e in _ops()
               if trace.kernel_of(e) == "griffin_spmm") * 1e-9
    assert trace.kernel_sum(red, "chunk_fn", "griffin_spmm") == \
        pytest.approx(spmm)
    mods = [e for e in EVENTS if e["kind"] == "module"]
    assert mods[0]["start_ns"] < lo        # began before the slice
    assert trace.module_sum(red, "chunk_fn") == pytest.approx(
        sum(min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
            for e in mods) * 1e-9)
    assert trace.module_sum(red, "chunk_fn", "n") == pytest.approx(3)
    assert trace.module_sum(red, "prefill_fn") == 0
    # the longest idle gap fell while the host was inside engine.step
    assert red["idle_gaps"][0][0] == "step"
    assert red["idle_gaps"][0][1] == max(g[1] for g in red["idle_gaps"])
    assert len(red["top_ops"]) <= 10
    assert not any(n.startswith(("while", "copy-start"))
                   for n, _ in red["top_ops"])


def _ev(plane, kind, name, start, dur):
    return {"plane": plane, "kind": kind, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_two_devices_average_and_collectives():
    ev = [_ev("host", "host", "bench.slice", 0, 1000),
          _ev("host", "host", "bench.idle", 600, 400)]
    for d in ("/device:TPU:0", "/device:TPU:1"):
        ev += [_ev(d, "module", "jit_chunk_fn(1)", 100, 400),
               _ev(d, "op", "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)",
                   100, 200),
               _ev(d, "op", "%all-gather.2 = bf16[8]{0} all-gather("
                   "bf16[2]{0} %q)", 300, 100),
               _ev(d, "op", "%copy-start.3 = (bf16[8]{0}) copy-start("
                   "bf16[8]{0} %r)", 100, 900)]
    red = trace.reduce(ev)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(300e-9)
    assert trace.module_sum(red, "chunk_fn") == pytest.approx(400e-9)
    assert trace.module_sum(red, "chunk_fn", "collective_s") == \
        pytest.approx(100e-9)
    gaps = dict((round(d * 1e9), lab) for lab, d in red["idle_gaps"])
    assert gaps == {100: "none", 600: "idle"}


def test_reduction_of_recorded_four_chip_slice():
    """Events of one decode chunk on each chip of a 1x4 mesh (minitron-8b
    widths, four layers, TPU v5e): dense_gemm told by its name under
    ``shard_map``, collectives summed, both averaged over the four chips."""
    events = json.loads((Path(__file__).parent / "data" /
                         "trace_events_4chips.json").read_text())
    red = trace.reduce(events)
    assert red["devices"] == 4
    ops = [e for e in events if e["kind"] == "op"]
    gemm = sum(e["dur_ns"] for e in ops
               if trace.kernel_of(e) == "dense_gemm") * 1e-9 / 4
    coll = sum(e["dur_ns"] for e in ops if trace.opcode(e["name"]) in
               ("all-gather", "all-reduce")) * 1e-9 / 4
    assert gemm > 0 and coll > 0
    assert trace.kernel_sum(red, "chunk_fn", "dense_gemm") == \
        pytest.approx(gemm)
    assert trace.module_sum(red, "chunk_fn", "collective_s") == \
        pytest.approx(coll)
    assert trace.module_sum(red, "chunk_fn", "n") == pytest.approx(1.0)
    assert not any(trace.kernel_of(e) == "griffin_spmm" for e in ops)
