"""Public entry of the live-KV decode attention kernel: arena views, the
per-step plan, and the blocks a decode step reads (the engine's counter)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import LANES, decode_attention_kernel, kv_plan

BLOCK_S = 256

_VIEW = (0, 1, 3, 4, 2)          # (L, B, S, KVH, hd) -> (L, B, KVH, hd, S)
_BACK = (0, 1, 4, 2, 3)


def position_minor(head_dim: int) -> bool:
    """True when the TPU keeps a (L, B, S, KVH, hd) arena position-minor,
    the layout the kernel reads in place.  The device's default layout
    moves the positions to the minor dimension when the head size is not a
    multiple of the 128 lanes (a bf16 (24, 10, 2048, 32, 64) arena is laid
    out {2,4,3,1,0}, and so is an f32 one) and keeps it row-major when it
    is; reading a row-major arena through the position-minor view would
    copy it.  tests/test_tpu_compile.py holds this rule to the compiler's
    layout for every transformer configuration."""
    return head_dim % LANES != 0


def step_plan(lengths: jax.Array, slots: jax.Array,
              block_s: int = BLOCK_S) -> jax.Array:
    """The kernel's plan for one decode step (``kernel.kv_plan``), built
    once and shared by every layer.  lengths: (B,) valid positions per row
    after the write, 0 for a dead row; slots: (B,) or scalar write
    positions."""
    B = lengths.shape[0]
    return kv_plan(lengths.astype(jnp.int32),
                   jnp.broadcast_to(slots, (B,)).astype(jnp.int32), block_s)


def kv_blocks(lengths: jax.Array, cache_len: int,
              block_s: int = BLOCK_S) -> jax.Array:
    """(2,) int32 per layer of one decode step: the KV blocks the kernel
    reads for rows of these lengths (a dead row, length 0, reads none) and
    the blocks the arena holds."""
    read = jnp.sum((lengths + block_s - 1) // block_s)
    return jnp.stack([read, jnp.asarray(
        lengths.shape[0] * (cache_len // block_s))]).astype(jnp.int32)


def live_kv_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      k_all: jax.Array, v_all: jax.Array, layer: jax.Array,
                      plan: jax.Array, *, block_s: int = BLOCK_S,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write each row's new K/V at its slot of layer ``layer`` of the
    stacked (L, B, S, KVH, hd) arena and attend, one query token per row,
    over the row's valid positions, as ``plan`` (``step_plan``, built with
    the same ``block_s``, which has to divide S) gives them.

    q: (B, 1, H, hd); k, v: (B, 1, KVH, hd).  Returns (out (B, 1, H, hd)
    in q's dtype, k_all, v_all); a row of length 0 writes nothing and its
    output is zeros.  The kernel works on the arena's position-minor view,
    which on the TPU is the arena's own layout (``position_minor``), so no
    view is copied."""
    B, _, H, hd = q.shape
    KVH = k_all.shape[3]
    heads_minor = lambda t: jnp.transpose(t[:, 0], (0, 2, 1))
    out, k_t, v_t = decode_attention_kernel(
        q.reshape(B, KVH, H // KVH, hd), heads_minor(k), heads_minor(v),
        jnp.transpose(k_all, _VIEW), jnp.transpose(v_all, _VIEW),
        jnp.asarray(layer, jnp.int32), plan, block_s=block_s,
        interpret=interpret)
    return (out.reshape(B, 1, H, hd), jnp.transpose(k_t, _BACK),
            jnp.transpose(v_t, _BACK))
