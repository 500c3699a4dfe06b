"""The benchmark's trace reduction, on events cut from a TPU v5e trace of
the stablelm.b80.chat cell (``data/trace_events.json``: the traced slice,
its host spans, three decode-chunk executions and 75 of their op events)
and on small synthetic traces."""
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
    spmm = [e for e in _ops() if trace.kernel_of(e) == "griffin_spmm"]
    assert len(spmm) == 10
    assert all('custom_call_target="tpu_custom_call"' in e["name"]
               for e in spmm)
    buffers = [e for e in _ops() if trace.opcode(e["name"]) == "custom-call"
               and trace.kernel_of(e) is None]
    assert buffers and all("AllocateBuffer" in e["name"] for e in buffers)
    dense = {"name": '%_dense_matmul_jit.3 = bf16[8,4096]{1,0} custom-call('
                     'bf16[8,4096]{1,0} %a, bf16[4096,4096]{1,0} %b), '
                     'custom_call_target="tpu_custom_call"'}
    assert trace.kernel_of(dense) == "dense_gemm"
    assert trace.kernel_of({"name": "x", "text": "_spmm_kernel"}) == \
        "griffin_spmm"


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
    """Events of one decode chunk on each chip of a 1x4 mesh (minitron-8b,
    TPU v5e): dense_gemm told by its operands, collectives summed, both
    averaged over the four chips."""
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
