"""Latency and rate arithmetic of the benchmark on synthetic stamps."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats  # noqa: E402


def _req(due, first, n, gap, admit=None, added=None):
    return {"due": due, "added": due if added is None else added,
            "admit": due if admit is None else admit,
            "times": [first + i * gap for i in range(n)]}


def _steady():
    # 10 requests due every 0.5 s; first token 0.1 s after due, then one
    # token every 10 ms
    return {i: _req(0.5 * i, 0.5 * i + 0.1, 20, 0.01) for i in range(10)}


def test_steady_stamps():
    m = stats.end_to_end(_steady(), seconds=10.0, end_s=10.0)
    assert m["ttft_p50_ms"] == pytest.approx(100.0)
    assert m["ttft_p75_ms"] == pytest.approx(100.0)
    assert m["tpot_p50_ms"] == pytest.approx(10.0)
    assert m["tpot_p90_ms"] == pytest.approx(10.0)
    assert m["tokens_per_s"] == pytest.approx(200 / 10.0)


def test_stall_inside_the_window_moves_every_metric():
    reqs = _steady()
    # a 2 s stall from t = 2.0: requests due in it wait, and the tokens of
    # one in flight stop for the stall's length
    for i in (4, 5, 6, 7, 8):
        reqs[i] = _req(0.5 * i, 4.0 + 0.1 * (i - 4), 20, 0.01)
    reqs[3]["times"] = [1.6 + 0.01 * k for k in range(10)] + \
        [4.0 + 0.01 * k for k in range(10)]
    steady = stats.end_to_end(_steady(), 10.0, 10.0)
    stalled = stats.end_to_end(reqs, 10.0, 10.0)
    assert stalled["ttft_p75_ms"] > steady["ttft_p75_ms"] + 900
    assert stalled["ttft_p50_ms"] > steady["ttft_p50_ms"]
    assert stalled["tpot_p90_ms"] > steady["tpot_p90_ms"]
    assert stats.tpot_s(reqs[3]) == pytest.approx((4.09 - 1.6) / 19)


def test_tokens_after_the_window_do_not_count():
    reqs = _steady()
    reqs[9]["times"] = [9.9, 9.95, 10.05, 10.2]
    m = stats.end_to_end(reqs, seconds=10.0, end_s=10.2)
    assert m["tokens_per_s"] == pytest.approx((180 + 2) / 10.0)


def test_request_that_never_answered_waits_to_the_end():
    reqs = _steady()
    reqs[9]["times"] = []
    assert stats.ttft_s(reqs[9], end_s=12.0) == pytest.approx(7.5)
    assert stats.tpot_s(reqs[9]) is None
    m = stats.end_to_end(reqs, 10.0, 12.0)
    assert m["ttft_p75_ms"] > 100.0


def test_queue_wait_and_generator_lag():
    reqs = {0: _req(1.0, 1.5, 3, 0.01, admit=1.25, added=1.003),
            1: _req(2.0, 2.1, 3, 0.01, admit=2.0, added=2.0)}
    assert stats.queue_waits_ms(reqs) == pytest.approx([250.0, 0.0])
    assert stats.lag_ms(reqs) == pytest.approx([3.0, 0.0])
