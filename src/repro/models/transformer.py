"""Decoder-only transformer family: dense (stablelm, command-r-plus,
llama3.2, minitron), VLM backbone (chameleon, early-fusion token ids), and
MoE (mixtral with SWA, llama4-scout top-1).

All layer stacks are ``lax.scan`` over stacked parameters so HLO size and
compile time are depth-independent at 100B scale; rematerialization is a
config knob.  Cross entropy is computed in sequence chunks so the
(B, S, vocab) logits tensor is never materialized (see models.losses).

Every weight GEMM goes through ``models.common.griffin_linear``: plain
arrays execute as ``x @ w`` (or the dense Pallas kernel under a
``sparse_execution`` scope), block-compacted ``GriffinWeights`` leaves
(from ``repro.sparsity.sparsify_params``) execute through the Sparse.B /
dual kernels — stacked per-layer compacted weights ride the same
``lax.scan`` (DESIGN.md Section 4).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.decode_attention import ops as live_kv
from .attention import attention, decode_attention
from .common import (act_fn, dense_init, execution_context, griffin_linear,
                     layer_norm1p, layer_scan, length_mask, paged_view,
                     paged_write, remat_fn, rms_norm, rope, stack_layers,
                     take_last, write_kv_layer)
from .moe import init_moe, moe_ffn

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    D, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    p: Params = {
        "ln1": jnp.zeros((D,), dt), "ln2": jnp.zeros((D,), dt),
        "wq": dense_init(ks[0], D, H * hd, dt),
        "wk": dense_init(ks[1], D, KVH * hd, dt),
        "wv": dense_init(ks[2], D, KVH * hd, dt),
        "wo": dense_init(ks[3], H * hd, D, dt),
    }
    if cfg.norm == "layernorm1p":
        p["ln1_b"] = jnp.zeros((D,), dt)
        p["ln2_b"] = jnp.zeros((D,), dt)
    if cfg.qk_norm:
        p["qn"] = jnp.zeros((hd,), dt)
        p["kn"] = jnp.zeros((hd,), dt)
    if cfg.moe:
        p["moe"] = init_moe(ks[4], D, cfg.d_ff, cfg.moe, dt)
    else:
        if cfg.gated_mlp:
            p["w_gate"] = dense_init(ks[5], D, cfg.d_ff, dt)
        p["w_up"] = dense_init(ks[6], D, cfg.d_ff, dt)
        p["w_down"] = dense_init(ks[7], cfg.d_ff, D, dt)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    params: Params = {
        "embed": dense_init(k_emb, cfg.vocab_size, cfg.d_model, dt, scale=1.0),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
        "layers": stack_layers(functools.partial(_init_layer, cfg),
                               k_layers, cfg.num_layers),
    }
    if cfg.norm == "layernorm1p":
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dt)
    return params


def unembed(cfg: ModelConfig, params: Params) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["head"]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: Params, name: str, x: jax.Array) -> jax.Array:
    """The block's norm ``name`` of ``p`` applied to ``x``: RMS norm, or
    LayerNorm1p with the bias leaf ``<name>_b`` (``cfg.norm``)."""
    if cfg.norm == "rms":
        return rms_norm(x, p[name], cfg.norm_eps)
    if cfg.norm == "layernorm1p":
        return layer_norm1p(x, p[name], p[name + "_b"], cfg.norm_eps)
    raise ValueError(f"unknown norm {cfg.norm!r}")


def _ffn(cfg: ModelConfig, p: Params, x: jax.Array, decode: bool = False,
         valid=None) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe:
        B, S, D = x.shape
        out, aux = moe_ffn(p["moe"], x.reshape(B * S, D), cfg.moe, cfg.act,
                           drop_free=decode,
                           valid=None if valid is None
                           else valid.reshape(B * S))
        return out.reshape(B, S, D), aux
    if cfg.gated_mlp:
        h = act_fn(cfg.act)(griffin_linear(x, p["w_gate"])) * \
            griffin_linear(x, p["w_up"])
    else:
        h = act_fn(cfg.act)(griffin_linear(x, p["w_up"]))
    return griffin_linear(h, p["w_down"]).astype(x.dtype), \
        jnp.zeros((), jnp.float32)


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    B, S, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = griffin_linear(x, p["wq"]).reshape(B, S, H, hd)
    k = griffin_linear(x, p["wk"]).reshape(B, S, KVH, hd)
    v = griffin_linear(x, p["wv"]).reshape(B, S, KVH, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta, cfg.rotary_frac)
    k = rope(k, positions, cfg.rope_theta, cfg.rotary_frac)
    return q, k, v


def block_train(cfg: ModelConfig, p: Params, x: jax.Array,
                positions: jax.Array, return_kv: bool = False, valid=None):
    """Full-sequence block (train / prefill).  ``valid`` is the optional
    (B, S) right-pad mask of the bucketed-prefill path: causal attention
    already keeps pads out of real positions (pads sit *after* every real
    token), so only the MoE dispatch needs it (pads must not consume expert
    capacity)."""
    h = _norm(cfg, p, "ln1", x)
    q, k, v = _qkv(cfg, p, h, positions)
    with jax.named_scope("attention"):
        o = attention(q, k, v, causal=True, window=cfg.window,
                      kv_chunk=cfg.kv_chunk)
    B, S, _, _ = q.shape
    x = x + griffin_linear(o.reshape(B, S, -1), p["wo"]).astype(x.dtype)
    h2 = _norm(cfg, p, "ln2", x)
    f, aux = _ffn(cfg, p, h2, valid=valid)
    x = (x + f).astype(x.dtype)
    return (x, aux, (k, v)) if return_kv else (x, aux)


def block_decode(cfg: ModelConfig, p: Params, x: jax.Array, k_all, v_all,
                 layer, pos, cache_len: int, plan=None):
    """One-token block of layer ``layer`` against the layer-stacked
    (L, B, S_cache, KVH, hd) caches; writes this layer's new K/V into them
    in place (``write_kv_layer``) and returns them updated.  Sliding-window
    archs use a rolling cache.

    ``pos`` is a scalar (lockstep batch, greedy_generate) or a (B,) vector
    of per-row positions (continuous-batching slot pools,
    runtime/engine.py): each row ropes, writes and masks at its own
    position; with equal entries the vector path is bit-identical to the
    scalar one (every op below is row-wise).

    ``plan`` (the kernel's ``step_plan``, which ``decode_step`` builds
    once per step where ``runs_live_kv`` holds) runs attention in the
    live-KV kernel (``kernels/decode_attention``), which
    writes the new K/V and reads each row's valid positions only; a row
    the plan marks dead writes and reads nothing and attends to zeros.
    Without it ``decode_attention`` reads the layer's whole cache."""
    h = _norm(cfg, p, "ln1", x)
    per_slot = pos.ndim > 0
    q, k, v = _qkv(cfg, p, h,
                   positions=pos[:, None] if per_slot else pos[None])
    with jax.named_scope("attention"):
        if plan is not None:
            o, k_all, v_all = live_kv.live_kv_attention(
                q, k, v, k_all, v_all, layer, plan,
                interpret=execution_context().interpret)
        else:
            slot, eff_pos, win = _cache_index(cfg, pos, cache_len)
            k_all = write_kv_layer(k_all, layer, k, slot)
            v_all = write_kv_layer(v_all, layer, v, slot)
            o = decode_attention(q, k_all[layer], v_all[layer], eff_pos,
                                 window=win)
    B = x.shape[0]
    x = x + griffin_linear(o.reshape(B, 1, -1), p["wo"]).astype(x.dtype)
    h2 = _norm(cfg, p, "ln2", x)
    f, _ = _ffn(cfg, p, h2, decode=True)
    return (x + f).astype(x.dtype), k_all, v_all


def _cache_index(cfg: ModelConfig, pos, cache_len: int):
    """(slot, eff_pos, window) of a decode step at ``pos`` on a fixed
    cache of ``cache_len`` positions: where the new K/V goes, the last
    valid position and the window left to mask.  Sliding-window archs
    whose cache fits the window roll it, and a rolling cache becomes fully
    valid once wrapped."""
    rolling = cfg.window is not None and cache_len <= cfg.window
    slot = jnp.where(rolling, pos % cache_len,
                     jnp.minimum(pos, cache_len - 1))
    eff_pos = jnp.where(rolling, jnp.minimum(pos, cache_len - 1), pos)
    return slot, eff_pos, None if rolling else cfg.window


def runs_live_kv(cfg: ModelConfig, cache_len: int, use_kernels: bool,
                 mesh) -> bool:
    """Whether ``block_decode`` serves attention over a fixed arena of
    ``cache_len`` positions with the live-KV kernel: Pallas kernels on one
    device (``use_kernels`` and no SPMD ``mesh``), no window narrower than
    the arena (a rolling cache has none), an arena of whole KV blocks
    (``BLOCK_S`` positions each), and one the device keeps position-minor
    (``position_minor``: a head size that is not a multiple of 128)."""
    no_window = cfg.window is None or cache_len <= cfg.window
    return (no_window and use_kernels and mesh is None
            and cache_len % live_kv.BLOCK_S == 0
            and live_kv.position_minor(cfg.hd))


def _live_kv_lengths(cfg: ModelConfig, cache: Params, live):
    """(lengths, slots) of the next decode step on ``cache`` where it runs
    the live-KV kernel (``runs_live_kv`` in the current execution scope),
    else None.  lengths: (B,) valid positions per row after the write,
    capped at the cache (a dead row's position keeps advancing), 0 for a
    row that ``live`` (optional (B,) bool) marks dead."""
    if "pages" in cache:
        return None
    B, S = cache["k"].shape[1:3]
    ctx = execution_context()
    if not runs_live_kv(cfg, S, ctx.use_kernels, ctx.spmd_mesh):
        return None
    slot, eff_pos, _ = _cache_index(cfg, cache["pos"] + 1, S)
    n = jnp.broadcast_to(jnp.minimum(eff_pos + 1, S), (B,))
    return (n if live is None else jnp.where(live, n, 0)), slot


def kv_blocks(cfg: ModelConfig, cache: Params, live=None) -> jax.Array:
    """(2,) int32: per layer, the KV blocks (``BLOCK_S`` positions, the
    last one partial where the arena is not whole blocks) the next decode
    step's attention reads on ``cache`` and the blocks its arena holds.
    The live-KV kernel reads the live rows' valid blocks; a fixed arena
    that keeps ``decode_attention`` (on a mesh, or a head size the kernel
    refuses) reads all of it, and so does a paged arena, whose
    ``paged_view`` gathers every row's ``max_pages * page_size``
    positions.  ``live`` as for ``decode_step``."""
    rows = _live_kv_lengths(cfg, cache, live)
    if rows is None:
        if "pages" in cache:
            B, max_pages = cache["pages"].shape
            S = max_pages * cache["k"].shape[2]
        else:
            B, S = cache["k"].shape[1:3]
        return jnp.full((2,), B * -(-S // live_kv.BLOCK_S), jnp.int32)
    return live_kv.kv_blocks(rows[0], cache["k"].shape[2])


def block_decode_paged(cfg: ModelConfig, p: Params, x: jax.Array, k_pool,
                       v_pool, k_scale, v_scale, pages, layer, pos,
                       page_size: int):
    """One-token block of layer ``layer`` against the layer-stacked paged
    KV pools (runtime/paging.py), written in place like ``block_decode``.

    Paging only activates when the arch has no effective sliding window at
    this cache length (discovery rule in runtime/paging.py), so the fixed
    path's rolling/eff-pos algebra collapses for every live row
    (``pos < max_pages * page_size``) to: write at ``pos``, attend with
    ``window=None`` — bit-identical to :func:`block_decode` on the gathered
    view.  ``k_scale``/``v_scale`` are None for fp32 pools."""
    h = _norm(cfg, p, "ln1", x)
    per_slot = pos.ndim > 0
    q, k, v = _qkv(cfg, p, h,
                   positions=pos[:, None] if per_slot else pos[None])
    with jax.named_scope("attention"):
        k_pool, k_scale = paged_write(k_pool, k_scale, pages, k, pos,
                                      page_size, layer)
        v_pool, v_scale = paged_write(v_pool, v_scale, pages, v, pos,
                                      page_size, layer)
        kc = paged_view(k_pool, k_scale, pages, x.dtype, layer)
        vc = paged_view(v_pool, v_scale, pages, x.dtype, layer)
        o = decode_attention(q, kc, vc, pos, window=None)
    B = x.shape[0]
    x = x + griffin_linear(o.reshape(B, 1, -1), p["wo"]).astype(x.dtype)
    h2 = _norm(cfg, p, "ln2", x)
    f, _ = _ffn(cfg, p, h2, decode=True)
    return (x + f).astype(x.dtype), k_pool, v_pool, k_scale, v_scale


# ---------------------------------------------------------------------------
# model-level functions
# ---------------------------------------------------------------------------

def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   return_kv: bool = False, lengths=None):
    """Embed + scan over layers.  Returns final hidden (and per-layer K/V
    stacked over layers when ``return_kv``).  ``lengths``: optional (B,)
    true prompt lengths of a right-padded batch (bucketed prefill)."""
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1])
    aux0 = jnp.zeros((), jnp.float32)
    valid = (None if lengths is None
             else length_mask(lengths, tokens.shape[1]))

    def body(carry, lp):
        x, aux = carry
        if return_kv:
            x, a, kv = block_train(cfg, lp, x, positions, return_kv=True,
                                   valid=valid)
            return (x, aux + a), kv
        x, a = block_train(cfg, lp, x, positions, valid=valid)
        return (x, aux + a), None

    fn = remat_fn(cfg, body)
    (x, aux), kvs = layer_scan(cfg.scan_layers, fn, (x, aux0),
                               params["layers"])
    x = _norm(cfg, params, "final_norm", x)
    return (x, aux, kvs) if return_kv else (x, aux)


def init_cache(cfg: ModelConfig, batch: int, length: int) -> Params:
    """Zeroed KV cache.  Sliding-window archs cap the cache at the window
    (rolling buffer), which is what makes long_500k decode O(window)."""
    clen = min(length, cfg.window) if cfg.window else length
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, batch, clen, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "pos": jnp.zeros((), jnp.int32)}


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache_len: Optional[int] = None,
            lengths: Optional[jax.Array] = None) -> Tuple[Params, jax.Array]:
    """Process a prompt, build the cache, return (cache, last-token logits).

    ``lengths``: optional (B,) true prompt lengths of a right-padded batch
    (bucketed prefill, DESIGN.md Section 9).  Pad K/V rows land in cache
    slots ``length..S-1`` — dead weight the decode loop overwrites slot
    ``pos`` *before* its position mask admits it, so they are never read.
    Requires the padded length to fit the cache (the bucket policy in
    runtime/engine.py clamps to it)."""
    B, S = tokens.shape
    x, _, (ks, vs) = forward_hidden(cfg, params, tokens, return_kv=True,
                                    lengths=lengths)
    clen = cache_len or S
    clen = min(clen, cfg.window) if cfg.window else clen
    if clen >= S:
        pad = clen - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:  # keep the last window
        assert lengths is None, "bucketed prefill must fit the cache window"
        ks, vs = ks[:, :, S - clen:], vs[:, :, S - clen:]
    if lengths is None:
        last, pos = x[:, -1], jnp.asarray(S - 1, jnp.int32)
    else:
        last = take_last(x, lengths)
        pos = (lengths - 1).astype(jnp.int32)          # per-row (B,) vector
    logits = griffin_linear(last, unembed(cfg, params))
    cache = {"k": ks, "v": vs, "pos": pos}
    return cache, logits


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, live=None) -> Tuple[jax.Array, Params]:
    """One decode step for the whole batch.  token: (B, 1) int32.

    A ``"pages"`` key marks a paged cache (runtime/paging.py): ``k``/``v``
    are then (L, num_pages, page_size, KVH, hd) pools indexed through the
    per-slot page table, with optional ``k_scale``/``v_scale`` leaves for
    int8 pools.  ``live``: optional (B,) bool of the rows whose logits are
    used; where the step runs the live-KV kernel (``runs_live_kv``) the
    other rows' attention reads nothing and their logits are garbage."""
    x = params["embed"][token]
    pos = cache["pos"] + 1
    if "pages" in cache:
        return _decode_step_paged(cfg, params, cache, x, pos)
    clen = cache["k"].shape[2]
    lengths = _live_kv_lengths(cfg, cache, live)
    plan = None if lengths is None else live_kv.step_plan(*lengths)

    def body(carry, lp):
        x, ks, vs, i = carry
        x, ks, vs = block_decode(cfg, lp, x, ks, vs, i, pos, clen, plan)
        return (x, ks, vs, i + 1), None

    (x, ks, vs, _), _ = layer_scan(
        cfg.scan_layers, body,
        (x, cache["k"], cache["v"], jnp.zeros((), jnp.int32)),
        params["layers"])
    x = _norm(cfg, params, "final_norm", x)
    logits = griffin_linear(x[:, 0], unembed(cfg, params))
    return logits, {"k": ks, "v": vs, "pos": pos}


def _decode_step_paged(cfg: ModelConfig, params: Params, cache: Params,
                       x: jax.Array, pos: jax.Array) -> Tuple[jax.Array, Params]:
    pages = cache["pages"]
    page_size = cache["k"].shape[2]
    int8 = "k_scale" in cache

    def body(carry, lp):
        x, kp, vp, ks_, vs_, i = carry
        x, kp, vp, ks_, vs_ = block_decode_paged(
            cfg, lp, x, kp, vp, ks_, vs_, pages, i, pos, page_size)
        return (x, kp, vp, ks_, vs_, i + 1), None

    carry = (x, cache["k"], cache["v"], cache.get("k_scale"),
             cache.get("v_scale"), jnp.zeros((), jnp.int32))
    (x, kp, vp, ks_, vs_, _), _ = layer_scan(cfg.scan_layers, body, carry,
                                             params["layers"])
    x = _norm(cfg, params, "final_norm", x)
    logits = griffin_linear(x[:, 0], unembed(cfg, params))
    out = {"pos": pos, "pages": pages, "k": kp, "v": vp}
    if int8:
        out["k_scale"], out["v_scale"] = ks_, vs_
    return logits, out
