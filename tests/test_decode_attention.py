"""Live-KV decode attention kernel (``kernels/decode_attention``) against
``models.attention.decode_attention``, in interpret mode on the CPU.

The kernel writes each row's new K/V into one layer of the stacked arena
and attends over the row's valid positions only; a dead row (length 0)
writes nothing and returns zeros.  Kernel-level cases cover ragged
lengths around the block edges, GQA and a layer of a stacked arena; model-
level cases run ``transformer.decode_step`` with the kernel and without it
(scalar positions, per-row positions, a rolling cache that has wrapped),
count the blocks a step reads, and check at published widths that only an
arena of whole KV blocks engages the kernel.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import ops
from repro.models import build_model, transformer
from repro.models.attention import decode_attention
from repro.models.common import sparse_execution

BF16 = jnp.bfloat16


def _arena(key, L, B, S, KVH, hd, dtype=BF16):
    ks = jax.random.split(key, 2)
    return (jax.random.normal(ks[0], (L, B, S, KVH, hd), dtype),
            jax.random.normal(ks[1], (L, B, S, KVH, hd), dtype))


# (B, S, KVH, H, hd, block_s, layer, lengths)
CASES = {
    # 0, 1, block_s - 1, block_s, block_s + 1 and S positions
    "ragged": (6, 64, 2, 2, 16, 16, 1, [0, 1, 15, 16, 17, 64]),
    "gqa": (4, 32, 2, 8, 16, 8, 0, [9, 0, 32, 1]),
    "one_block": (3, 24, 1, 2, 8, 24, 2, [24, 5, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_decode_attention(case):
    B, S, KVH, H, hd, block_s, layer, lengths = CASES[case]
    L = 3
    key = jax.random.PRNGKey(sorted(CASES).index(case))
    k_all, v_all = _arena(key, L, B, S, KVH, hd)
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 1), 3)
    q = jax.random.normal(kq, (B, 1, H, hd), BF16)
    k = jax.random.normal(kk, (B, 1, KVH, hd), BF16)
    v = jax.random.normal(kv, (B, 1, KVH, hd), BF16)
    n = jnp.asarray(lengths, jnp.int32)
    slots = jnp.maximum(n - 1, 0)
    dead = n == 0
    # a dead row's KV is never read: poison it
    k_all = k_all.at[:, dead].set(jnp.nan)
    v_all = v_all.at[:, dead].set(jnp.nan)

    out, k2, v2 = ops.live_kv_attention(
        q, k, v, k_all, v_all, jnp.int32(layer),
        ops.step_plan(n, slots, block_s), block_s=block_s, interpret=True)

    rows = jnp.flatnonzero(~dead)
    want_k = k_all.at[layer, rows, slots[rows]].set(k[rows, 0])
    want_v = v_all.at[layer, rows, slots[rows]].set(v[rows, 0])
    np.testing.assert_array_equal(np.asarray(k2, np.float32),
                                  np.asarray(want_k, np.float32))
    np.testing.assert_array_equal(np.asarray(v2, np.float32),
                                  np.asarray(want_v, np.float32))
    got = np.asarray(out, np.float32)
    assert np.isfinite(got).all()
    assert (got[np.asarray(dead)] == 0).all()
    live = np.asarray(rows)
    want = decode_attention(q[live], want_k[layer][live], want_v[layer][live],
                            slots[live])
    np.testing.assert_allclose(got[live], np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_kv_blocks_counts_live_blocks():
    """``transformer.kv_blocks``: the next step's rows of lengths 0 (dead),
    1, 255, 256, 257 and 2048 (a position past the arena caps there) read
    0 + 1 + 1 + 1 + 2 + 8 blocks of 256 and the arena holds 6 x 8.  With
    kernels off, or an arena that is not whole blocks (its last block
    partial), ``decode_attention`` reads the whole arena: 6 x 8 of 6 x 8."""
    cfg = get_config("stablelm-1.6b").reduced()

    def cache(S):                      # only the arena's shape is read
        return {"k": jnp.zeros((1, 6, S, 1, 1)),
                "pos": jnp.asarray([0, -1, 253, 254, 255, 3000], jnp.int32)}
    live = jnp.asarray([False, True, True, True, True, True])
    with sparse_execution(use_kernels=True, interpret=True):
        got = transformer.kv_blocks(cfg, cache(2048), live)
        part = transformer.kv_blocks(cfg, cache(2000), live)
    off = transformer.kv_blocks(cfg, cache(2048), live)
    assert list(np.asarray(got)) == [0 + 1 + 1 + 1 + 2 + 8, 6 * 8]
    assert list(np.asarray(part)) == list(np.asarray(off)) == [6 * 8, 6 * 8]
    assert ops.position_minor(64) and not ops.position_minor(128)


@pytest.mark.parametrize("cache_len", [1921, 2048])
def test_kernel_engages_on_whole_blocks_only(cache_len):
    """stablelm-1.6b at published widths, 10 slots: the kernel runs on an
    arena of whole 256-position blocks and not on one of a length the
    serving CLI derives (longest prompt + generation cap + 1, here 1921),
    whose rows would not fit the chip's VMEM as one block; that arena keeps
    ``decode_attention``."""
    from repro.runtime.engine import _promote_arena
    api = build_model(get_config("stablelm-1.6b"))
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: _promote_arena(api.init_cache(10, cache_len), 10))
    args = (params, cache, jax.ShapeDtypeStruct((10, 1), jnp.int32),
            jax.ShapeDtypeStruct((10,), jnp.bool_))
    with sparse_execution(use_kernels=True, interpret=True):
        text = str(jax.make_jaxpr(
            lambda p, c, t, l: api.decode_step(p, c, t, live=l))(*args))
    assert ("name=decode_attention" in text) == (cache_len % 256 == 0)


def _decode_case(case):
    """(cfg, cache, token, live) of a small transformer decode step."""
    base = get_config("mixtral-8x7b" if case == "rolling_wrapped"
                      else "stablelm-1.6b").reduced()
    cfg = dataclasses.replace(base, dtype="bfloat16")
    B, L, S = 4, cfg.num_layers, 256
    if case == "rolling_wrapped":           # a rolling cache of the window
        cfg = dataclasses.replace(cfg, window=S)
    kv = _arena(jax.random.PRNGKey(7), L, B, S, cfg.num_kv_heads, cfg.hd)
    if case == "scalar_pos":
        pos = jnp.asarray(20, jnp.int32)
    elif case == "vector_pos":
        pos = jnp.asarray([3, 255, 16, 200], jnp.int32)
    else:                                  # positions past the window
        pos = jnp.asarray([5, 289, 530, 287], jnp.int32)
    cache = {"k": kv[0], "v": kv[1], "pos": pos}
    token = jnp.asarray([[3], [7], [11], [2]], jnp.int32)
    live = None if case == "scalar_pos" else jnp.asarray([True, True, False,
                                                          True])
    return cfg, cache, token, live


@pytest.mark.parametrize("case", ["scalar_pos", "vector_pos",
                                  "rolling_wrapped"])
def test_decode_step_kernel_matches_plain_attention(case, monkeypatch):
    cfg, cache, token, live = _decode_case(case)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    assert transformer.runs_live_kv(cfg, cache["k"].shape[2], True, None)
    with sparse_execution(use_kernels=True, interpret=True):
        logits, new = api.decode_step(params, cache, token, live=live)
        monkeypatch.setattr(transformer, "runs_live_kv",
                            lambda *a, **k: False)
        want_logits, want = api.decode_step(params, cache, token)
    keep = np.ones(token.shape[0], bool) if live is None else np.asarray(live)
    np.testing.assert_allclose(np.asarray(logits, np.float32)[keep],
                               np.asarray(want_logits, np.float32)[keep],
                               atol=5e-2, rtol=5e-2)
    # every kept row's K/V lands where the plain path writes it; a dead
    # row's does not move
    for name in ("k", "v"):
        got, exp, old = (np.asarray(t[name], np.float32)
                         for t in (new, want, cache))
        np.testing.assert_array_equal(got[:, keep], exp[:, keep])
        np.testing.assert_array_equal(got[:, ~keep], old[:, ~keep])
