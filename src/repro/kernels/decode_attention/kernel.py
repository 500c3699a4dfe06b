"""Decode attention over the layer-stacked fixed slot arena, reading only
live KV (DESIGN.md Section 9).

One query token per row writes its new K/V into one layer of the arena and
attends to the first ``len_b`` positions of its row, in place: the kernel
takes the whole stacked cache as the decode layer loop carries it and
returns it (aliased), and the layer index, the per-row lengths and write
slots arrive together as one int32 scalar-prefetch vector.  The cache is
read through its position-minor view (L, B, KVH, hd, S): that is how the
TPU lays out a (.., S, KVH, hd) arena whose head size is not a multiple of
128, and an XLA scatter into that layout would first copy the whole arena
into another, so the write happens here too.

Grid: (B, S // block_s).  Blocks at or past a row's last live block map to
that block again, so the pipeline issues no new DMA, and are not computed
(``pl.when``); a dead row (length 0) maps to the block the grid already
holds, writes nothing and returns zeros.  The plan of lengths, rows,
block ranges and slots is built once per decode step (``kv_plan``); only
the layer index changes between layers.  The new token's column is set in
the VMEM block before the block is read, and the tile of positions holding
it is copied back to the arena.  Scores and softmax are f32 (online,
across blocks); p . V accumulates in f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ...configs.platform import checked_interpret

NEG_INF = -1e30
LANES = 128


def _with_column(tile: jax.Array, new: jax.Array, lane) -> jax.Array:
    """The (KVH, hd, width) ``tile`` with position ``lane`` set to the new
    token's values ``new``, given as (hd, KVH)."""
    kvh, hd, width = tile.shape
    # spread[h, d, l] = new[d, h]: one one-hot product per head moves each
    # head's values onto the sublanes and across the lanes (exact: one
    # nonzero term per output)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (kvh, kvh, width), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (kvh, kvh, width), 1))
    spread = jax.lax.dot_general(
        jnp.broadcast_to(new, (kvh, hd, kvh)), eye.astype(new.dtype),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 2)
    return jnp.where(lanes == lane, spread, tile.astype(jnp.float32)
                     ).astype(tile.dtype)


def _attn_kernel(sp_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, ko_hbm,
                 vo_hbm, m_ref, l_ref, acc_ref, tiles, sems, *, batch: int,
                 block_s: int, nblk: int, width: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n = sp_ref[1 + b]
    slot = sp_ref[1 + 4 * batch + b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_s < n)
    def _block():
        off = slot % block_s
        for c in range(block_s // width):       # the tile holding the slot
            @pl.when((slot // block_s == j) & (off // width == c))
            def _write():
                tile = pl.ds(c * width, width)
                dst = pl.ds(pl.multiple_of(j * block_s + c * width, width),
                            width)
                # the copy goes out of a scratch tile: Pallas cannot
                # slice a DMA source out of a block with squeezed dims
                copies = []
                for i, (blk, new, out) in enumerate(
                        ((k_ref, kn_ref, ko_hbm), (v_ref, vn_ref, vo_hbm))):
                    tiles[i] = _with_column(blk[:, :, tile], new[...],
                                            off % width)
                    blk[:, :, tile] = tiles[i]
                    cp = pltpu.make_async_copy(
                        tiles.at[i], out.at[sp_ref[0], b, :, :, dst],
                        sems.at[i])
                    cp.start()
                    copies.append(cp)
                for cp in copies:
                    cp.wait()

        # (KVH, G, hd) . (KVH, hd, block_s) -> (KVH, G, block_s)
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) / scale
        pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = pos < n
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        # (KVH, G, block_s) . (KVH, hd, block_s) -> (KVH, G, hd), in f32
        pv = jax.lax.dot_general(
            p, v_ref[...].astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _flush():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def kv_plan(lengths: jax.Array, slots: jax.Array, block_s: int) -> jax.Array:
    """The kernel's per-step plan, the same in every layer: one int32
    vector [len_0..len_B-1, src.., lo.., hi.., slot..].  Per row: its valid
    positions and write slot, the arena row its grid steps read and the
    range [lo, hi] of blocks they map to.  A live row walks blocks
    0..last; a dead row repeats the block the grid holds when it arrives
    (the previous live row's last block, or the first live row's block 0),
    so it costs no DMA."""
    B = lengths.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    live = lengths > 0
    last = jnp.maximum(pl.cdiv(lengths, block_s) - 1, 0).astype(jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(live, rows,
                    jnp.where(prev >= 0, prev, jnp.argmax(live)))
    lo = jnp.where(live | (prev < 0), 0, last[src])
    hi = jnp.where(live, last, lo)
    return jnp.concatenate([lengths, src, lo, hi, slots]).astype(jnp.int32)


def decode_attention_kernel(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                            k_t: jax.Array, v_t: jax.Array, layer: jax.Array,
                            plan: jax.Array, *, block_s: int,
                            interpret: bool = False):
    """Write one token per row into layer ``layer`` of the arena and
    attend to each row's valid positions.

    q:            (B, KVH, G, hd) — query heads grouped onto their KV head.
    k_new, v_new: (B, hd, KVH) — the token's K/V, head size leading.
    k_t, v_t:     (L, B, KVH, hd, S) — the stacked arena, position-minor.
    layer:        int32 scalar.
    plan:         (5B,) int32 from ``kv_plan`` over each row's valid
                  positions after the write (0 = dead row: no write, zero
                  output) and the position it writes, below its length.
    Returns (out (B, KVH, G, hd) in q's dtype, k_t, v_t written).
    """
    B, KVH, G, hd = q.shape
    S = k_t.shape[-1]
    assert S % block_s == 0, (S, block_s)
    nblk = S // block_s
    width = LANES if block_s % LANES == 0 else block_s
    # one scalar-prefetch operand: [layer] + the plan
    sp = jnp.concatenate([jnp.reshape(layer, (1,)).astype(jnp.int32), plan])

    def kv_map(b, j, sp):
        blk = jnp.minimum(jnp.maximum(j, sp[1 + 2 * B + b]), sp[1 + 3 * B + b])
        return (sp[0], sp[1 + B + b], 0, 0, blk)

    kv_spec = pl.BlockSpec((None, None, KVH, hd, block_s), kv_map)
    row_spec = pl.BlockSpec((None, KVH, G, hd), lambda b, j, sp: (b, 0, 0, 0))
    new_spec = pl.BlockSpec((None, hd, KVH), lambda b, j, sp: (b, 0, 0))
    arena = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_attn_kernel, batch=B, block_s=block_s, nblk=nblk,
                          width=width, scale=math.sqrt(hd)),
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nblk),
            in_specs=[row_spec, new_spec, new_spec, kv_spec, kv_spec],
            out_specs=[row_spec, arena, arena],
            scratch_shapes=[pltpu.VMEM((KVH, G, 1), jnp.float32),
                            pltpu.VMEM((KVH, G, 1), jnp.float32),
                            pltpu.VMEM((KVH, G, hd), jnp.float32),
                            pltpu.VMEM((2, KVH, hd, width), k_t.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
                   jax.ShapeDtypeStruct(k_t.shape, k_t.dtype),
                   jax.ShapeDtypeStruct(v_t.shape, v_t.dtype)],
        # operand indices count the scalar-prefetch vector
        input_output_aliases={4: 1, 5: 2},
        interpret=checked_interpret(interpret),
    )(sp, q, k_new, v_new, k_t, v_t)
