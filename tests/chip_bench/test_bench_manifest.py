"""BENCHMARK.json against the rules the benchmark is held to: names,
units and keys, what each per-layer metric moves and where it is read,
the share of four-chip cells, and that every file it names exists."""
import importlib
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
ENTRY = {"configs": {"name", "source", "file", "reduced", "why"},
         "workloads": {"name", "config", "traffic", "chips", "why"},
         "end_to_end": {"name", "unit", "better", "bound", "source"},
         "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


@pytest.fixture(scope="module")
def man():
    return common.manifest()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(man):
    assert set(man) == KEYS
    assert man["command"][:3] == ["python3", "-m", "bench.run"]
    assert len(man["command"]) <= 32 and all(_line(w) for w in man["command"])
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(man["run_seconds"], int) and \
        1 <= man["run_seconds"] <= 51
    assert len(json_bytes(man)) <= 64 * 1024


def json_bytes(man):
    return (ROOT / "BENCHMARK.json").read_bytes()


@pytest.mark.parametrize("section", sorted(ENTRY))
def test_entries_have_their_keys_and_valid_names(man, section):
    entries = man[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY[section] <= set(e) <= ENTRY[section] | extra, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
            assert e["source"] in SOURCES
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end" and section != "per_layer":
                assert _line(e[k])


def test_configs(man):
    used = {w["config"] for w in man["workloads"]}
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files) and len(man["configs"]) <= 24
    for c in man["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        conf = common.load_json(ROOT / c["file"])
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"] and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_workloads(man):
    conf_names = {c["name"] for c in man["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(pairs) <= 24
    for w in man["workloads"]:
        assert w["config"] in conf_names and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and _line(w["why"])
        assert (common.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        conf = common.config_file(man, w["config"])
        assert conf["deployment"]["chips"] == w["chips"]
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


def _reports(man, metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_metrics(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
        for cell in m.get("workloads", cells):
            assert cell in cells and _reports(man, e2e[m["moves"]], cell)
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
    layers = {}
    for m in man["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        reported = [m for m in man["end_to_end"] if _reports(man, m, cell)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(_reports(man, m, cell) for m in man["per_layer"])


def test_roofline_and_mfu_names(man):
    for m in man["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    moved = {m["moves"] for m in man["per_layer"]
             if m["name"].endswith("_roofline")}
    mfu = {m["moves"] for m in man["per_layer"] if "mfu" in m["name"]}
    assert moved <= mfu
