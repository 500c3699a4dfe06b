"""DSE results cache: content-hashed round trips, invalidation, robustness."""
import json
import os

import numpy as np
import pytest

from repro.core import CoreConfig, Mode
from repro.core.dse import (ResultsCache, design_fingerprint, sweep)
from repro.core.evaluate import DEFAULT_MASK_MODEL, MaskModel
from repro.core.spec import GRIFFIN, SPARSE_B_STAR, sparse_b

CORE = CoreConfig()
DESIGNS = [SPARSE_B_STAR, sparse_b(2, 1, 0, shuffle=True), GRIFFIN]


def test_sweep_cache_round_trip(tmp_path):
    cache = ResultsCache(str(tmp_path / "cache"))
    cold = sweep(DESIGNS, Mode.B, CORE, seed=1, cache=cache)
    assert cache.hits == 0 and cache.misses == len(DESIGNS)
    warm = sweep(DESIGNS, Mode.B, CORE, seed=1, cache=cache)
    assert cache.hits == len(DESIGNS)
    assert warm == cold                     # exact round trip through JSON
    # and identical to an uncached sweep
    assert sweep(DESIGNS, Mode.B, CORE, seed=1) == cold


def test_fingerprint_sensitivity():
    base = design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 1,
                              DEFAULT_MASK_MODEL)
    assert base == design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 1,
                                      DEFAULT_MASK_MODEL)
    others = [
        design_fingerprint(SPARSE_B_STAR, Mode.A, CORE, 1, DEFAULT_MASK_MODEL),
        design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 2, DEFAULT_MASK_MODEL),
        design_fingerprint(sparse_b(4, 0, 1), Mode.B, CORE, 1,
                           DEFAULT_MASK_MODEL),
        design_fingerprint(SPARSE_B_STAR, Mode.B, CoreConfig(k0=32), 1,
                           DEFAULT_MASK_MODEL),
        design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 1,
                           MaskModel(chan_cv=0.7)),
        design_fingerprint(GRIFFIN, Mode.B, CORE, 1, DEFAULT_MASK_MODEL),
    ]
    assert len(set(others + [base])) == len(others) + 1


def test_corrupt_cache_entry_is_a_miss(tmp_path):
    cache = ResultsCache(str(tmp_path / "cache"))
    designs = DESIGNS[:1]
    cold = sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    # corrupt every entry on disk
    for fn in os.listdir(cache.path):
        with open(os.path.join(cache.path, fn), "w") as f:
            f.write("{not json")
    again = sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    assert again == cold                    # recomputed, not poisoned
    # and the entry was repaired in place
    fn = os.path.join(cache.path, os.listdir(cache.path)[0])
    assert json.load(open(fn)) == cold[0]


def test_cache_get_put_direct(tmp_path):
    cache = ResultsCache(str(tmp_path / "c"))
    assert cache.get("deadbeef") is None
    row = {"design": "x", "speedup": 1.25}
    cache.put("deadbeef", row)
    assert cache.get("deadbeef") == row


def test_fingerprint_includes_config_schema_version(monkeypatch):
    """The candidate-config schema version is part of every sweep
    fingerprint: a schema bump must cold-start rows cached under the old
    schema, because scores are only comparable within one schema."""
    import repro.core.dse as dse

    base = design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 1,
                              DEFAULT_MASK_MODEL)
    monkeypatch.setattr(dse, "CONFIG_SCHEMA_VERSION",
                        dse.CONFIG_SCHEMA_VERSION + 1)
    bumped = design_fingerprint(SPARSE_B_STAR, Mode.B, CORE, 1,
                                DEFAULT_MASK_MODEL)
    assert bumped != base


def test_schema_bump_cold_starts_sweep_cache(tmp_path, monkeypatch):
    """Regression: rows cached under an older CONFIG_SCHEMA_VERSION are
    misses for the current code (and vice versa), never silent hits."""
    import repro.core.dse as dse

    cache = ResultsCache(str(tmp_path / "cache"))
    designs = DESIGNS[:2]
    monkeypatch.setattr(dse, "CONFIG_SCHEMA_VERSION", 1)   # "old" schema
    old = sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    assert (cache.hits, cache.misses) == (0, 2)

    monkeypatch.setattr(dse, "CONFIG_SCHEMA_VERSION", 2)   # schema bump
    new = sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    assert (cache.hits, cache.misses) == (0, 4)            # cold again
    assert new == old                       # same physics, fresh rows

    # each schema's rows now hit under their own version only
    sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    assert (cache.hits, cache.misses) == (2, 4)
    monkeypatch.setattr(dse, "CONFIG_SCHEMA_VERSION", 1)
    sweep(designs, Mode.B, CORE, seed=1, cache=cache)
    assert (cache.hits, cache.misses) == (4, 4)
