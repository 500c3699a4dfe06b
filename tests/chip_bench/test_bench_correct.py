"""The check that decides ``correct``, driven through the rest of a run at
the tiny rehearsal size on the CPU (the harness's look for a chip is the
only part skipped): a sound run passes, the float8 control put in the
program's place does not, and each fault a cell can have, planted under the
timed path, makes ``correct`` come out false.  Every configuration file is
driven, a four-chip one on four virtual devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

from bench import rehearse  # noqa: E402

PAIRS = {name: conf for name, conf, _ in rehearse.pairs()}


def _chips(name):
    return PAIRS[name]["deployment"]["chips"]


def _one_chip(name, fault, control=False):
    import bench_faults
    return bench_faults.tiny_cycle(name, fault, control=control)


def _four_chips(name, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, str(HERE / "bench_faults.py"), name,
                        fault], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(PAIRS))
def test_sound_run_is_correct_and_control_reads_wider(name):
    """The control reads wider than the limit, so it comes out not correct."""
    if _chips(name) == 1:
        chk = _one_chip(name, "none", control=True)
        assert chk["control_correct"] is False
        assert chk["control_gap"] > PAIRS[name]["check"]["served_gap_limit"]
    else:
        chk = _four_chips(name, "none")
    assert chk["correct"] and chk["failed"] == 0 and chk["compiles"] == 0
    assert chk["tokens"] >= rehearse.tiny_conf(PAIRS[name])["check"][
        "min_tokens"]


def _faults():
    """Each fault a cell can have: a token altered where it is produced and
    a step that leaves its state unchanged everywhere; the exchange
    between chips left out where the configuration spans chips."""
    out = []
    for name in sorted(PAIRS):
        faults = ["token_altered", "state_unchanged"]
        if _chips(name) > 1:
            faults += ["exchange_left_out"]
        out += [(name, f) for f in faults]
    return out


@pytest.mark.parametrize("name,fault", _faults())
def test_fault_makes_run_incorrect(name, fault):
    chk = _one_chip(name, fault) if _chips(name) == 1 \
        else _four_chips(name, fault)
    assert not chk["correct"]
    assert chk["served_gap"] > PAIRS[name]["check"]["served_gap_limit"]
