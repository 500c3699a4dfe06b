"""Substrate tests: data determinism, optimizer, compression, checkpoint
round-trip + resharding, elastic planning, straggler logic, losses."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, synth_batch
from repro.models.losses import chunked_cross_entropy
from repro.optim import adamw
from repro.optim.compression import compressed_psum_tree, init_error
from repro.runtime.elastic import plan_mesh, reshard
from repro.runtime.straggler import (StragglerConfig, StragglerDetector,
                                     reassign_shards)


SMALL = ShapeConfig("small", 16, 8, "train")


def test_data_deterministic_and_sharded():
    cfg = get_config("llama3.2-1b").reduced()
    b1 = synth_batch(cfg, SMALL, DataConfig(seed=7, num_shards=2, shard_id=0),
                     step=3)
    b2 = synth_batch(cfg, SMALL, DataConfig(seed=7, num_shards=2, shard_id=0),
                     step=3)
    b3 = synth_batch(cfg, SMALL, DataConfig(seed=7, num_shards=2, shard_id=1),
                     step=3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (4, 16)
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()


def test_adamw_descends_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                            weight_decay=0.0, clip_norm=10.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw.init(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw.apply(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.2
    assert m["grad_norm"] > 0


def test_compressed_psum_matches_mean():
    kwargs = {}
    if hasattr(jax.sharding, "AxisType"):    # added after jax 0.4.x
        kwargs["axis_types"] = (jax.sharding.AxisType.Auto,)
    mesh = jax.make_mesh((1,), ("data",), **kwargs)
    g = {"a": jnp.asarray(np.random.RandomState(0).randn(32).astype(np.float32))}
    err = init_error(g)

    from jax.sharding import PartitionSpec as P

    def f(gg, ee):
        return compressed_psum_tree(gg, ee, mesh, ("data",))

    red, new_err = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P()))(g, err)
    # single shard: mean == dequantized self; error = quantization residual
    np.testing.assert_allclose(np.asarray(red["a"]), np.asarray(g["a"]),
                               atol=float(jnp.abs(g["a"]).max()) / 100)
    assert float(jnp.abs(new_err["a"]).max()) <= \
        float(jnp.abs(g["a"]).max()) / 127 + 1e-6


def test_error_feedback_converges():
    """Repeated compression of the same gradient loses nothing on average."""
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(64).astype(np.float32))
    from repro.optim.compression import quantize, dequantize
    e = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for i in range(50):
        q, s = quantize(g + e)
        d = dequantize(q, s)
        e = (g + e) - d
        acc = acc + d
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g), atol=1e-3)


def test_checkpoint_roundtrip_and_retention(tmp_path):
    state = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "n": {"m": jnp.ones((4,), jnp.bfloat16)},
             "step": jnp.asarray(7, jnp.int32)}
    d = str(tmp_path / "ckpt")
    for s in (1, 2, 3, 4):
        save(d, s, state, keep=2)
    assert latest_step(d) == 4
    assert len([x for x in os.listdir(d) if x.startswith("step_")]) == 2
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
    out = restore(d, template)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(state["w"]))
    assert out["n"]["m"].dtype == jnp.bfloat16


def test_checkpoint_restores_onto_new_mesh(tmp_path):
    """Elastic restart: save replicated, restore sharded on a fresh mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    d = str(tmp_path / "ck")
    save(d, 1, state)
    mesh = plan_mesh(1, 1)
    sh = {"w": NamedSharding(mesh, P())}
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
    out = restore(d, template, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8))


def test_plan_mesh_shapes():
    m = plan_mesh(1, 1)
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_straggler_detection_and_reassignment():
    det = StragglerDetector(4, StragglerConfig(threshold=1.5, evict_after=3))
    for step in range(5):
        for h in range(4):
            det.record(h, 1.0 if h != 2 else 3.0)
        det.observe()           # one streak advance per closed step
    assert det.stragglers() == [2]
    assert det.evictions() == [2]
    plan = reassign_shards(8, [0, 1, 3])
    assert sorted(sum(plan.values(), [])) == list(range(8))
    assert 2 not in plan


def test_chunked_ce_matches_full():
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(2, 10, 8).astype(np.float32))
    u = jnp.asarray(rng.randn(8, 32).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 32, (2, 10)), jnp.int32)
    lab = lab.at[0, :3].set(-1)        # masked positions
    full = h @ u
    lse = jax.nn.logsumexp(full, axis=-1)
    gold = jnp.take_along_axis(full, jnp.maximum(lab, 0)[..., None],
                               axis=-1)[..., 0]
    ref = ((lse - gold) * (lab >= 0)).sum() / (lab >= 0).sum()
    for chunk in (3, 5, 10, 16):
        got = chunked_cross_entropy(h, u, lab, chunk)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


def test_train_step_descends_tiny_model():
    from repro.models import build_model
    from repro.runtime.train import init_state, make_train_step
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    state = init_state(api, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        api, adamw.AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=50)))
    dc = DataConfig(seed=0)
    losses = []
    for i in range(8):
        batch = {k: jnp.asarray(v)
                 for k, v in synth_batch(cfg, SMALL, dc, step=0).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses
