"""The benchmark's traffic generator: a seed fixes the schedule, every seed
offers the same set of sizes and gaps, and every draw stays in its clip."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import common, traffic  # noqa: E402

MIX = {"arrivals": "poisson", "rate_rps": 4.0, "schedule_seed": 7,
       "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                         "min": 32, "max": 1536},
       "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                         "min": 16, "max": 384}}


def _key(plan):
    return [(p.rid, p.due_s, p.prompt.tobytes(), p.max_new) for p in plan]


def test_same_seed_same_schedule():
    a = traffic.draw(MIX, 2 ** 31 + 7, 40, 100352)
    b = traffic.draw(MIX, 2 ** 31 + 7, 40, 100352)
    assert _key(a) == _key(b)


def test_seeds_share_the_schedule_and_draw_their_own_tokens():
    a = traffic.draw(MIX, 11, 40, 1000)
    b = traffic.draw(MIX, 12, 40, 1000)
    assert len(a) == len(b) == traffic.count(MIX, 40) == 160
    assert [(p.due_s, len(p.prompt), p.max_new) for p in a] == \
        [(p.due_s, len(p.prompt), p.max_new) for p in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_schedule_seed_reorders_the_same_sizes_and_gaps():
    a = traffic.draw(MIX, 11, 40, 1000)
    b = traffic.draw(dict(MIX, schedule_seed=8), 11, 40, 1000)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    ga, gb = (np.sort(np.diff([0.0] + [p.due_s for p in x])) for x in (a, b))
    np.testing.assert_allclose(ga, gb, rtol=1e-9)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


@pytest.mark.parametrize("seed", [0, 5, 2 ** 33 + 1])
def test_draws_clipped_and_due_in_window(seed):
    plan = traffic.draw(MIX, seed, 40, 100352)
    plens = np.array([len(p.prompt) for p in plan])
    olens = np.array([p.max_new for p in plan])
    assert plens.min() >= 32 and plens.max() <= 1536
    assert olens.min() >= 16 and olens.max() <= 384
    assert plens.max() == 1536 and olens.max() == 384    # tails reach clips
    assert 400 < np.median(plens) < 640 and 100 < np.median(olens) < 160
    due = np.array([p.due_s for p in plan])
    assert np.all(np.diff(due) > 0) and due[0] > 0 and due[-1] < 40
    ids = np.concatenate([p.prompt for p in plan])
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 100352


def test_prefill_buckets_cover_prompt_range():
    assert traffic.prompt_buckets(32, 1536) == [32, 64, 128, 256, 512, 1024,
                                               2048]
    assert traffic.warm_lengths(MIX) == [32, 64, 128, 256, 512, 1024, 1536]


def test_committed_mixes_fit_their_arenas():
    man = common.manifest()
    for wl in man["workloads"]:
        mix = common.traffic_file(wl["traffic"])
        conf = common.config_file(man, wl["config"])
        assert traffic.longest(mix) <= conf["deployment"]["cache_len"]
