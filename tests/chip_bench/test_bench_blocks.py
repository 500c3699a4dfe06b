"""A configuration's block comes from its file: the reference, the weight
rules and the GEMM count all follow ``bench.blocks``, and every ``arch``
key reaches the program.

The stablelm block's reference and weights are held to values recorded
before the block moved into ``bench/blocks/swiglu_decoder.py``
(``data/swiglu_tiny_reference.npz``, ``data/swiglu_tiny_weights.json``:
the tiny rehearsal configuration, seed 2**31 + 77).  The toy block
``blocks/relu2_decoder.py`` beside this file (a LayerNorm1p with a bias,
an ungated squared-ReLU MLP) is found as a new module and nothing else."""
import copy
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import (blocks, common, loop, reference, rehearse,  # noqa: E402
                   weights, work)

SEED = 2 ** 31 + 77
DATA = HERE / "data"


def _stablelm():
    return common.load_json(common.BENCH / "configs" /
                            "stablelm-1.6b-b80.json")


@pytest.fixture(scope="module")
def tiny_swiglu():
    from repro.models import build_model
    conf = rehearse.tiny_conf(_stablelm())
    shapes = jax.eval_shape(build_model(loop.program_config(conf)).init,
                            jax.random.PRNGKey(0))
    return conf, weights.make(shapes, conf, SEED)


@pytest.fixture(scope="module")
def swiglu_readings(tiny_swiglu):
    conf, w = tiny_swiglu
    arch = conf["arch"]
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, 40).astype(np.int32)
    served = rng.integers(0, 512, 24).astype(np.int32)
    block = blocks.name_of(conf)
    logits_at = blocks.load(block).logits_at
    seq = np.concatenate([prompt, served]).astype(np.int32)
    idx = np.arange(39, 64, 3, dtype=np.int32)
    return {
        "gaps": reference.served_gaps(w, block, arch, prompt, served, 256,
                                      32),
        "control_gaps": reference.served_gaps(w, block, arch, prompt,
                                              served, 256, 32, control=True),
        "logits": jax.jit(lambda w, t, i: logits_at(w, t, i, arch))(
            w, seq, idx),
        "control_logits": jax.jit(
            lambda w, t, i: logits_at(w, t, i, arch, quant=True))(
            w, seq, idx)}


@pytest.mark.parametrize("what", ["gaps", "control_gaps", "logits",
                                  "control_logits"])
def test_swiglu_reference_reads_as_before_the_move(swiglu_readings, what):
    assert blocks.name_of(_stablelm()) == "swiglu_decoder"
    before = np.load(DATA / "swiglu_tiny_reference.npz")[what]
    np.testing.assert_array_equal(np.asarray(swiglu_readings[what]), before)


def test_swiglu_weights_read_as_before_the_move(tiny_swiglu):
    _, w = tiny_swiglu
    got = {jax.tree_util.keystr(p): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()
        for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == json.loads((DATA / "swiglu_tiny_weights.json").read_text())


# --- a block added as a new module only --------------------------------

TOY = {"name": "toy", "block": "relu2_decoder",
       "arch": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 4, "head_dim": 16, "d_ff": 256,
                "vocab_size": 128, "norm_eps": 1e-5, "dtype": "float32"},
       "deployment": {"chips": 1, "slots": 4},
       "pruning": {"weight_sparsity": 0.0, "block_k": 128, "block_n": 128,
                   "unit": 128, "pruned": []},
       "init": {"norm_std": 0.1, "embed_std": 1.0}}


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(blocks, "__path__",
                        [*blocks.__path__, str(HERE / "blocks")])
    monkeypatch.delitem(sys.modules, "bench.blocks.relu2_decoder",
                        raising=False)
    yield blocks.load("relu2_decoder")
    sys.modules.pop("bench.blocks.relu2_decoder", None)


def _toy_shapes(extra=()):
    a = TOY["arch"]
    d, f, v, n = a["d_model"], a["d_ff"], a["vocab_size"], a["num_layers"]
    hw = a["num_heads"] * a["head_dim"]
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    layer = {"ln1": sd(n, d), "ln1_b": sd(n, d), "ln2": sd(n, d),
             "ln2_b": sd(n, d), "wq": sd(n, d, hw), "wk": sd(n, d, hw),
             "wv": sd(n, d, hw), "wo": sd(n, hw, d), "w_up": sd(n, d, f),
             "w_down": sd(n, f, d)}
    layer.update({k: sd(n, d, f) for k in extra})
    return {"embed": sd(v, d), "final_norm": sd(d), "final_norm_b": sd(d),
            "head": sd(d, v), "layers": layer}


def test_new_block_brings_its_weight_rules(toy):
    w = weights.make(_toy_shapes(), TOY, 3)
    for b in ("ln1_b", "ln2_b"):
        assert 0.05 < float(jnp.std(w["layers"][b])) < 0.2   # norm_std
    assert 0.05 < float(jnp.std(w["final_norm_b"])) < 0.2
    up = w["layers"]["w_up"]
    assert float(jnp.std(up)) == pytest.approx(1 / 8, rel=0.05)  # 1/sqrt(d)
    # a leaf the block does not list has no rule, in either block
    with pytest.raises(ValueError, match="w_gate"):
        weights.make(_toy_shapes(extra=("w_gate",)), TOY, 3)
    with pytest.raises(ValueError, match="_b' .kind None. in block 'swi"):
        weights.make(_toy_shapes(), dict(TOY, block="swiglu_decoder"), 3)


def _greedy(toy, w, prompt, n):
    arch = TOY["arch"]
    seq = list(prompt)
    for _ in range(n):
        t = jnp.asarray(seq, jnp.int32)
        lg = toy.logits_at(w, t, jnp.asarray([len(seq) - 1]), arch)
        seq.append(int(jnp.argmax(lg[0])))
    return np.asarray(seq[len(prompt):], np.int32)


def test_new_block_brings_its_reference(toy):
    w = weights.make(_toy_shapes(), TOY, 4)
    arch = TOY["arch"]
    prompt = np.arange(5, 17, dtype=np.int32)
    served = _greedy(toy, w, prompt, 6)
    gaps = reference.served_gaps(w, "relu2_decoder", arch, prompt, served,
                                 32, 8)
    np.testing.assert_allclose(gaps, 0.0, atol=1e-5)
    # a token the toy's reference does not put first reads its own gap
    off = served.copy()
    off[2] = (off[2] + 1) % arch["vocab_size"]
    got = reference.served_gaps(w, "relu2_decoder", arch, prompt, off, 32, 8)
    seq = np.concatenate([prompt, off[:2]])
    lg = toy.logits_at(w, jnp.asarray(seq), jnp.asarray([len(seq) - 1]),
                       arch)[0]
    want = (lg.max() - lg[off[2]]) / jnp.std(lg)
    assert float(want) > 0
    assert got[2] == pytest.approx(float(want), rel=1e-5)
    ctl = reference.served_gaps(w, "relu2_decoder", arch, prompt, served,
                                32, 8, control=True)
    assert np.isfinite(ctl).all() and (ctl >= 0).all()


def test_new_block_brings_its_gemm_count(toy):
    a = TOY["arch"]
    d, f, hw = a["d_model"], a["d_ff"], a["num_heads"] * a["head_dim"]
    assert work.gemm_shapes(TOY) == toy.gemm_shapes(a)
    step = work.decode_step(TOY)
    # four attention matrices and two MLP ones a layer, not SwiGLU's three
    assert step["params"] == a["num_layers"] * (4 * d * hw + 2 * d * f) \
        + d * a["vocab_size"]
    swiglu = work.decode_step(dict(TOY, block="swiglu_decoder"))
    assert swiglu["params"] - step["params"] == a["num_layers"] * d * f
    assert work.lower_bound_s(TOY, {"bf16_flops": 197e12,
                                    "hbm_bytes_per_s": 819e9}) > 0


def test_unknown_block_is_refused():
    with pytest.raises(ModuleNotFoundError):
        work.decode_step(dict(TOY, block="no_such_block"))
    with pytest.raises(ValueError, match="no module name"):
        blocks.load("../reference")


# --- arch keys reach the program --------------------------------------

def test_program_config_refuses_an_arch_key_the_program_lacks():
    conf = copy.deepcopy(_stablelm())
    conf["arch"]["mlp_kind"] = "relu2"
    with pytest.raises(ValueError, match="mlp_kind"):
        loop.program_config(conf)


def test_program_config_passes_every_arch_key():
    conf = copy.deepcopy(_stablelm())
    cfg = loop.program_config(conf)
    assert all(getattr(cfg, k) == v for k, v in conf["arch"].items())
    conf["arch"]["qk_norm"] = True
    assert loop.program_config(conf).qk_norm is True
    tiny = rehearse.tiny_conf(conf)
    assert tiny["arch"]["qk_norm"] is True
    assert loop.program_config(tiny).d_model == 128
