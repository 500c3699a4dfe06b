"""Paths, manifest and data-file loaders, and the small measuring helpers
every part of the benchmark shares.  Nothing here imports the program
under test."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent      # the checkout
BENCH = ROOT / "bench"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in man['workloads']]})")


def config_file(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def program_on_path() -> None:
    """Make the program under test (``src/repro``) importable."""
    import sys
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile ``q`` (0-100) of ``values``."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class CompileClock:
    """Counts XLA compiles and their seconds (JAX's own monitoring event):
    a compile inside the measured window shows up here."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def peak_bytes() -> int:
    """Peak device memory of the fullest chip."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in jax.devices())
