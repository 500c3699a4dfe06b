"""Minitron-8B's Nemotron-4 block in the program, held to the plain
reference ``bench/blocks/nemotron_decoder.py`` at a tiny size on the CPU:
LayerNorm1p with a bias, the ungated squared-ReLU MLP and rotary on half
of each head; prefill and decode through the fixed and the paged arena;
the 1x4 mesh engine against one device (``tests/nemotron_mesh.py``, on
four virtual CPU devices); and stablelm-1.6b's programs, which the new
fields must leave as they were."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from bench import blocks, common, loop, rehearse, weights, work  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import build_model, transformer  # noqa: E402
from repro.models.common import (griffin_linear, layer_norm1p,  # noqa: E402
                                 rope, sparse_execution)
from repro.runtime.engine import (  # noqa: E402
    _batch_axes, _make_paged_insert, _promote_arena)
from repro.runtime.paging import (PageAllocator, build_spec,  # noqa: E402
                                  paged_tree)
from repro.runtime.serve import make_decode_chunk_fn  # noqa: E402
from repro.sparsity import sparsify_params  # noqa: E402

SEED = 2 ** 31 + 16
# Program and reference both compute in float32 on the same weights; they
# differ only in the order of their sums (the program's chunked attention
# and its Pallas GEMM tiles against the reference's plain dots), which
# moves logits of magnitude up to about 5 by a few float32 ulps (3.6e-6
# measured).  1e-4 leaves 25x room, and rounding the weights to bfloat16,
# the next precision down, moves the logits by far more (test below).
TOL = 1e-4
PROMPT, TOTAL = 20, 48


def _conf():
    return common.load_json(common.BENCH / "configs" /
                            "minitron-8b-dense.json")


@pytest.fixture(scope="module")
def tiny():
    """(conf, api, weights, tokens, reference logits at every position)."""
    conf = rehearse.tiny_conf(_conf())
    api = build_model(loop.program_config(conf))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    w = weights.make(shapes, conf, SEED)
    seq = jnp.asarray(np.random.default_rng(16).integers(
        0, conf["arch"]["vocab_size"], TOTAL), jnp.int32)
    ref = blocks.of(conf).logits_at(w, seq, jnp.arange(TOTAL), conf["arch"])
    return conf, api, w, seq, ref


def _prefill_logits(api, w, seq):
    with sparse_execution(use_kernels=True, interpret=True):
        h, _ = transformer.forward_hidden(api.cfg, w, seq[None])
        return griffin_linear(h[0], w["head"])


# --- the pieces -------------------------------------------------------

def test_layer_norm1p_is_the_formula():
    rng = np.random.default_rng(0)
    x, w, b = (rng.standard_normal(s).astype(np.float32)
               for s in ((3, 64), (64,), (64,)))
    x64 = x.astype(np.float64)
    mu = x64.mean(-1, keepdims=True)
    var = ((x64 - mu) ** 2).mean(-1, keepdims=True)
    want = (x64 - mu) / np.sqrt(var + 1e-5) * (1.0 + w) + b
    got = layer_norm1p(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    half = layer_norm1p(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w),
                        jnp.asarray(b), 1e-5)
    assert half.dtype == jnp.bfloat16


def _rope_by_hand(x, pos, theta, rot):
    """numpy float64: the first ``rot`` channels rotated in two halves,
    frequencies theta^(-i / (rot/2)), the rest as they were."""
    half = rot // 2
    freqs = theta ** (-np.arange(half) / half)
    ang = pos[:, None] * freqs
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x = x.astype(np.float64)
    x1, x2 = x[..., :half], x[..., half:rot]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., rot:]], -1)


@pytest.mark.parametrize("frac", [0.5, 1.0])
def test_rope_by_hand(frac):
    x = np.random.default_rng(1).standard_normal((2, 7, 3, 16)).astype(
        np.float32)
    pos = np.arange(7)
    got = rope(jnp.asarray(x), jnp.asarray(pos), 500.0, frac)
    np.testing.assert_allclose(np.asarray(got),
                               _rope_by_hand(x, pos, 500.0, int(16 * frac)),
                               atol=1e-5)


def test_partial_rope_passes_the_rest_through():
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 7, 3, 16)), jnp.bfloat16)
    pos = jnp.arange(7)
    got = rope(x, pos, 10000.0, 0.5)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # the rotated part is a whole-width rope of a head of 8 channels
    np.testing.assert_array_equal(np.asarray(got[..., :8]),
                                  np.asarray(rope(x[..., :8], pos, 10000.0)))
    # fraction 1.0 is the rope every other configuration runs
    np.testing.assert_array_equal(np.asarray(rope(x, pos, 10000.0, 1.0)),
                                  np.asarray(rope(x, pos, 10000.0)))


# --- the configuration ------------------------------------------------

def test_minitron_config_is_the_published_one():
    cfg = get_config("minitron-8b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.hd, cfg.d_ff, cfg.vocab_size) == (32, 4096, 48, 8, 128,
                                                  16384, 256000)
    assert (cfg.norm, cfg.gated_mlp, cfg.act, cfg.rotary_frac,
            cfg.tie_embeddings) == ("layernorm1p", False, "relu2", 0.5,
                                    False)
    # the benchmark's file states every field it serves, and no other
    assert loop.program_config(_conf()) == cfg
    # non-embedding parameters: 32 x (attention 58.7e6 + MLP 134.2e6)
    assert build_model(cfg).param_count_total() == 32 * (
        2 * 4096 * 6144 + 2 * 4096 * 1024 + 2 * 4096 * 16384)


def test_init_has_biases_and_no_gate():
    api = build_model(get_config("minitron-8b").reduced())
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    assert {"ln1_b", "ln2_b", "w_up", "w_down"} <= set(shapes["layers"])
    assert "w_gate" not in shapes["layers"] and "final_norm_b" in shapes
    assert set(weights.leaf_name(p) for p, _ in
               jax.tree_util.tree_flatten_with_path(shapes)[0]) == \
        set(blocks.load("nemotron_decoder").LEAVES)
    plain = build_model(get_config("stablelm-1.6b").reduced())
    layer = jax.eval_shape(plain.init, jax.random.PRNGKey(0))["layers"]
    assert "w_gate" in layer and "ln1_b" not in layer


def test_decode_step_work_by_hand():
    conf = _conf()
    step = work.decode_step(conf)
    per_layer = 4096 * 6144 * 2 + 4096 * 1024 * 2 + 4096 * 16384 * 2
    assert step["params"] == 32 * per_layer + 4096 * 256000   # 7.22e9
    # each chip streams a quarter of the bf16 weights a step (3.61e9 B),
    # the 16 slots' input rows whole and its quarter of the output rows
    acts = sum(c * (16 * k * 2 + 16 * n * 2 / 4)
               for _, k, n, c in work.gemm_shapes(conf))
    assert step["bytes"] == step["params"] * 2 / 4 + acts


def test_reference_refuses_another_block():
    conf = rehearse.tiny_conf(_conf())
    arch = dict(conf["arch"], gated_mlp=True)
    with pytest.raises(ValueError, match="gated_mlp"):
        blocks.of(conf).logits_at({}, jnp.zeros(4, jnp.int32),
                                  jnp.arange(4), arch)


# --- the program against the reference ---------------------------------

def test_prefill_logits_match_reference(tiny):
    conf, api, w, seq, ref = tiny
    got = _prefill_logits(api, w, seq)
    assert float(jnp.max(jnp.abs(got - ref))) <= TOL


def test_tolerance_fails_a_lower_precision(tiny):
    """The same program on weights rounded to bfloat16, and the float8
    control, both miss the reference by more than the tolerance."""
    conf, api, w, seq, ref = tiny
    w16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype), w)
    assert float(jnp.max(jnp.abs(_prefill_logits(api, w16, seq) - ref))) \
        > 10 * TOL
    ctl = blocks.of(conf).logits_at(w, seq, jnp.arange(TOTAL), conf["arch"],
                                    quant=True)
    assert float(jnp.max(jnp.abs(ctl - ref))) > 10 * TOL


def _fixed_decode(api, w, seq, clen):
    cache, logits = api.prefill(w, {"tokens": seq[None, :PROMPT]},
                                cache_len=clen)
    step = jax.jit(api.decode_step)
    out = [logits[0]]
    for t in range(PROMPT, TOTAL - 1):
        logits, cache = step(w, cache, seq[None, t:t + 1])
        out.append(logits[0])
    return jnp.stack(out)


def _paged_decode(api, w, seq, clen, page_size=16):
    spec, clen = build_spec(api, 1, clen, page_size, None, "fp32")
    arena = paged_tree(_promote_arena(api.init_cache(1, clen), 1), 1, spec)
    sub, logits = api.prefill(w, {"tokens": seq[None, :PROMPT]},
                              cache_len=clen)
    ids = PageAllocator(spec.num_pages).reserve(spec.pages_needed(TOTAL))
    insert = _make_paged_insert(_batch_axes(api, clen), spec)
    cache, _, _, _ = insert(
        arena, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32),
        sub, logits, jnp.asarray(0), jnp.asarray(TOTAL - PROMPT),
        jnp.asarray(spec.page_row(ids)))
    step = jax.jit(api.decode_step)
    out = [logits[0]]
    for t in range(PROMPT, TOTAL - 1):
        logits, cache = step(w, cache, seq[None, t:t + 1])
        out.append(logits[0])
    return jnp.stack(out)


@pytest.mark.parametrize("arena", ["fixed", "paged"])
def test_decode_through_the_cache_matches_reference(tiny, arena):
    """Prefill 20 tokens, then decode the rest teacher-forced: the logits
    at positions 19..46 against the reference's full forward pass.  The
    arena holds 64 positions, not whole 256-position blocks, so attention
    reads it whole, as at published widths (head size 128)."""
    conf, api, w, seq, ref = tiny
    with sparse_execution(use_kernels=True, interpret=True):
        got = (_fixed_decode if arena == "fixed" else _paged_decode)(
            api, w, seq, 64)
    assert float(jnp.max(jnp.abs(got - ref[PROMPT - 1:TOTAL - 1]))) <= TOL


# --- one device against a 1x4 mesh ------------------------------------

@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, str(HERE / "nemotron_mesh.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arena", ["fixed", "paged"])
def test_mesh_tokens_bit_identical_to_one_device(mesh_run, arena):
    one, mesh = mesh_run[f"one_{arena}"], mesh_run[f"mesh_{arena}"]
    assert len(one) == 6 and all(len(t) >= 6 for t in one.values())
    assert mesh == one
    assert mesh_run["one_fixed"] == mesh_run["one_paged"]


@pytest.mark.parametrize("arena", ["fixed", "paged"])
def test_mesh_runs_the_kernels_under_shard_map(mesh_run, arena):
    got = mesh_run[f"dispatch_{arena}"]
    assert got.get("shard_map", 0) > 0
    assert got.get("spmd_oracle", 0) == 0 and got.get("plain", 0) == 0


def test_mesh_engine_records_spans_and_counters(mesh_run):
    names = {n for n, _ in mesh_run["spans"]}
    assert {"engine.tick", "engine.chunk", "engine.sync"} <= names
    st = mesh_run["stats_fixed"]
    assert st["live_rows"] > 0
    assert st["emitted"] == st["prefill_calls"] + st["live_rows"]
    prefills = [a for n, a in mesh_run["spans"] if n == "engine.prefill"]
    assert st["prefill_tokens"] == sum(a["prompt_len"] for a in prefills) > 0
    chunks = [a for n, a in mesh_run["spans"] if n == "engine.chunk"]
    assert sum(a["chunk"] for a in chunks) == st["decode_steps"]


def test_whole_arena_path_counts_the_whole_arena(mesh_run):
    """On a mesh attention reads the fixed arena whole: 4 slots x one
    (partial) block of 256 positions per layer and step.  The paged arena
    gathers the same view for every row, so it counts the same."""
    for arena in ("fixed", "paged"):
        st = mesh_run[f"stats_{arena}"]
        assert st["kv_blocks_read"] == st["kv_blocks_arena"] \
            == st["decode_steps"] * 4 * 1 > 0


# --- stablelm-1.6b lowers as before ------------------------------------

# sha256 of the StableHLO text of stablelm-1.6b (reduced) with kernels on,
# recorded on the tree before the new ModelConfig fields
BEFORE = {
    "dense": (
        "d295bca5e7f8edb2fce1eb15ad53fe017c05ec24e9e970b1dcce9fb746549f5a",
        "d4dd948def9ee414fa23c3bfdd63e58628bd571159df6d351c0b7d70baa276a0"),
    "sparse_b": (
        "4a43ddf431fe4378e384948af842e729226735e32659df0a2a7d0fa57512f8f1",
        "94b95788890719db5fd11d4f652c2ae85b5bc31135ed22d2c03cd2bd4bac4b3e"),
}


@pytest.mark.parametrize("kind", sorted(BEFORE))
def test_stablelm_programs_lower_as_before(kind):
    """The fused decode chunk (8 steps, 4 slots x 256) and a bucketed
    prefill, dense and Sparse.B-compacted."""
    api = build_model(get_config("stablelm-1.6b").reduced())
    params = api.init(jax.random.PRNGKey(0))
    if kind == "sparse_b":
        params = sparsify_params(params, 0.5, compact=True, block_k=16,
                                 block_n=32, unit=32)
    cache = _promote_arena(api.init_cache(4, 256), 4)
    batch = {"tokens": jnp.zeros((1, 64), jnp.int32),
             "lengths": jnp.full((1,), 40, jnp.int32)}
    with sparse_execution(use_kernels=True, interpret=True):
        chunk = jax.jit(make_decode_chunk_fn(api, 8)).lower(
            params, cache, jnp.zeros((4, 1), jnp.int32),
            jnp.zeros((4,), jnp.int32)).as_text()
        prefill = jax.jit(lambda p, b: api.prefill(p, b, cache_len=256)
                          ).lower(params, batch).as_text()
    got = tuple(hashlib.sha256(t.encode()).hexdigest()
                for t in (chunk, prefill))
    assert got == BEFORE[kind]


# --- the cell's per-layer metrics --------------------------------------

def test_mesh_metrics_read_the_recorded_four_chip_slice():
    """``collective_ms_per_step`` and ``gemm_roofline`` find their events
    in a recorded decode chunk of a 1x4 mesh (TPU v5e, minitron-8b widths,
    tests/chip_bench/data), and the collective reader finds nothing on
    one chip."""
    from bench import trace
    from bench.metrics import collective_ms_per_step, gemm_roofline
    data = HERE / "chip_bench" / "data"
    run = {"conf": _conf(), "peak": common.peaks("TPU v5 lite"), "chips": 4,
           "slice": {"decode_steps": 8}}
    four = trace.reduce(json.loads(
        (data / "trace_events_4chips.json").read_text()))
    assert collective_ms_per_step.read(run, four) > 0
    assert gemm_roofline.read(run, four) > 0
    one = trace.reduce(json.loads((data / "trace_events.json").read_text()))
    assert collective_ms_per_step.read(run, one) is None
    assert gemm_roofline.read(run, one) is None
