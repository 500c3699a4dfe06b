"""Whisper-large-v3 backbone [arXiv:2212.04356]: 32-layer encoder + 32-layer
decoder, d=1280, 20 heads, GeLU MLPs.

The conv audio frontend is a STUB per the assignment: ``input_specs()``
supplies precomputed frame embeddings (B, 1500, d) — the post-conv mel
representation.  The encoder adds sinusoidal positions and runs
bidirectional attention; the decoder is causal with cross-attention (we use
rope for decoder self-attention since the assigned shapes exceed Whisper's
learned 448-position table — recorded as a deviation in DESIGN.md
Section 7).  Weight GEMMs route through ``models.common.griffin_linear``
(the conv frontend stub and attention score/context products do not — they
are not weight GEMMs, DESIGN.md Section 5).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from .attention import attention, decode_attention
from .common import (act_fn, dense_init, griffin_linear, layer_scan,
                     paged_view, paged_write, rms_norm, rope, stack_layers,
                     take_last, write_kv_slot)

Params = Dict[str, Any]


def _sinusoid(length: int, d: int) -> jax.Array:
    pos = np.arange(length)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=1),
                       jnp.float32)


def _init_attn(cfg, key, kv_dim=None):
    dt = jnp.dtype(cfg.dtype)
    D, H, hd = cfg.d_model, cfg.num_heads, cfg.hd
    kv_dim = kv_dim or D
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], D, H * hd, dt),
            "wk": dense_init(ks[1], kv_dim, H * hd, dt),
            "wv": dense_init(ks[2], kv_dim, H * hd, dt),
            "wo": dense_init(ks[3], H * hd, D, dt)}


def _init_mlp(cfg, key):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    return {"w_up": dense_init(ks[0], cfg.d_model, cfg.d_ff, dt),
            "w_down": dense_init(ks[1], cfg.d_ff, cfg.d_model, dt)}


def _init_enc_layer(cfg, key):
    ks = jax.random.split(key, 2)
    return {"ln1": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.dtype)),
            "attn": _init_attn(cfg, ks[0]),
            "ln2": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.dtype)),
            "mlp": _init_mlp(cfg, ks[1])}


def _init_dec_layer(cfg, key):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {"ln1": jnp.zeros((cfg.d_model,), dt),
            "self": _init_attn(cfg, ks[0]),
            "ln_x": jnp.zeros((cfg.d_model,), dt),
            "cross": _init_attn(cfg, ks[1]),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "mlp": _init_mlp(cfg, ks[2])}


def init_params(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "embed": dense_init(ks[0], cfg.vocab_size, cfg.d_model, dt, scale=1.0),
        "enc_layers": stack_layers(functools.partial(_init_enc_layer, cfg),
                                   ks[1], cfg.encoder_layers),
        "dec_layers": stack_layers(functools.partial(_init_dec_layer, cfg),
                                   ks[2], cfg.num_layers),
        "enc_norm": jnp.zeros((cfg.d_model,), dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
        "head": dense_init(ks[3], cfg.d_model, cfg.vocab_size, dt),
    }


def _mha(cfg, p, xq, xkv, *, causal, positions=None, kv_chunk):
    B, Sq, D = xq.shape
    H, hd = cfg.num_heads, cfg.hd
    q = griffin_linear(xq, p["wq"]).reshape(B, Sq, H, hd)
    k = griffin_linear(xkv, p["wk"]).reshape(B, xkv.shape[1], H, hd)
    v = griffin_linear(xkv, p["wv"]).reshape(B, xkv.shape[1], H, hd)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, causal=causal, kv_chunk=kv_chunk)
    return griffin_linear(o.reshape(B, Sq, -1),
                          p["wo"]).astype(xq.dtype), (k, v)


def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """frames: (B, F, d) precomputed post-conv embeddings (frontend stub)."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def body(x, lp):
        h, _ = _mha(cfg, lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                    rms_norm(x, lp["ln1"], cfg.norm_eps), causal=False,
                    kv_chunk=cfg.kv_chunk)
        x = (x + h).astype(x.dtype)
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f = griffin_linear(act_fn(cfg.act)(
            griffin_linear(h2, lp["mlp"]["w_up"])), lp["mlp"]["w_down"])
        return (x + f).astype(x.dtype), None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = layer_scan(cfg.scan_layers, fn, x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   frames: jax.Array, return_kv: bool = False):
    """Decoder over tokens with cross-attention to the encoded frames."""
    enc = encode(cfg, params, frames)
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1])

    def body(x, lp):
        h, kv = _mha(cfg, lp["self"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                     rms_norm(x, lp["ln1"], cfg.norm_eps), causal=True,
                     positions=positions, kv_chunk=cfg.kv_chunk)
        x = (x + h).astype(x.dtype)
        hx, xkv = _mha(cfg, lp["cross"], rms_norm(x, lp["ln_x"], cfg.norm_eps),
                       enc, causal=False, kv_chunk=cfg.kv_chunk)
        x = (x + hx).astype(x.dtype)
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f = griffin_linear(act_fn(cfg.act)(
            griffin_linear(h2, lp["mlp"]["w_up"])), lp["mlp"]["w_down"])
        out = (x + f).astype(x.dtype)
        return out, (kv, xkv) if return_kv else None

    fn = jax.checkpoint(body) if (cfg.remat and not return_kv) else body
    x, kvs = layer_scan(cfg.scan_layers, fn, x, params["dec_layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    return (x, aux, kvs) if return_kv else (x, aux)


def init_cache(cfg: ModelConfig, batch: int, length: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    L, H, hd, F = cfg.num_layers, cfg.num_heads, cfg.hd, cfg.enc_frames
    return {
        "k": jnp.zeros((L, batch, length, H, hd), dt),
        "v": jnp.zeros((L, batch, length, H, hd), dt),
        "xk": jnp.zeros((L, batch, F, H, hd), dt),
        "xv": jnp.zeros((L, batch, F, H, hd), dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            frames: jax.Array, cache_len=None, lengths=None):
    """``lengths``: optional (B,) true prompt lengths of a right-padded
    batch (bucketed prefill, DESIGN.md Section 9).  Decoder self-attention
    is causal, so real positions never see the pads; pad K/V rows sit in
    slots ``length..S-1`` where the decode loop overwrites slot ``pos``
    before its position mask admits it."""
    B, S = tokens.shape
    x, _, kvs = forward_hidden(cfg, params, tokens, frames, return_kv=True)
    (ks, vs), (xks, xvs) = kvs
    clen = cache_len or S
    pad = clen - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    if lengths is None:
        last, pos = x[:, -1], jnp.asarray(S - 1, jnp.int32)
    else:
        last = take_last(x, lengths)
        pos = (lengths - 1).astype(jnp.int32)          # per-row (B,) vector
    logits = griffin_linear(last, params["head"])
    return {"k": ks, "v": vs, "xk": xks, "xv": xvs, "pos": pos}, logits


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, live=None):
    """``cache["pos"]`` is a scalar (lockstep batch) or a (B,) vector of
    per-row positions (continuous-batching slot pools, runtime/engine.py).
    ``live`` (the rows whose logits are used) is not needed here."""
    x = params["embed"][token]
    pos = cache["pos"] + 1
    per_slot = pos.ndim > 0
    B = x.shape[0]
    H, hd = cfg.num_heads, cfg.hd
    # "pages" marks a paged self-attention cache (runtime/paging.py): k/v
    # become (L, num_pages, page_size, H, hd) pools indexed through the slot
    # page table; the cross-attention xk/xv leaves stay fixed (encoder K/V
    # is written once at admission, never grows).
    paged = "pages" in cache
    pages = cache.get("pages")
    page_size = cache["k"].shape[2]
    int8 = "k_scale" in cache

    def body(x, xs):
        if paged and int8:
            lp, kc, vc, kscale, vscale, xk, xv = xs
        else:
            lp, kc, vc, xk, xv = xs
            kscale = vscale = None
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        posv = pos[:, None] if per_slot else pos[None]
        q = rope(griffin_linear(h, lp["self"]["wq"]).reshape(B, 1, H, hd),
                 posv, cfg.rope_theta)
        k = rope(griffin_linear(h, lp["self"]["wk"]).reshape(B, 1, H, hd),
                 posv, cfg.rope_theta)
        v = griffin_linear(h, lp["self"]["wv"]).reshape(B, 1, H, hd)
        if paged:
            kc, kscale = paged_write(kc, kscale, pages, k, pos, page_size)
            vc, vscale = paged_write(vc, vscale, pages, v, pos, page_size)
            o = decode_attention(q, paged_view(kc, kscale, pages, x.dtype),
                                 paged_view(vc, vscale, pages, x.dtype), pos)
        else:
            kc = write_kv_slot(kc, k, pos)
            vc = write_kv_slot(vc, v, pos)
            o = decode_attention(q, kc, vc, pos)
        x = (x + griffin_linear(o.reshape(B, 1, -1),
                                lp["self"]["wo"])).astype(x.dtype)
        # cross attention against the static encoder K/V
        hx = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        qx = griffin_linear(hx, lp["cross"]["wq"]).reshape(B, 1, H, hd)
        ox = decode_attention(qx, xk, xv, jnp.asarray(xk.shape[1] - 1))
        x = (x + griffin_linear(ox.reshape(B, 1, -1),
                                lp["cross"]["wo"])).astype(x.dtype)
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f = griffin_linear(act_fn(cfg.act)(
            griffin_linear(h2, lp["mlp"]["w_up"])), lp["mlp"]["w_down"])
        if paged and int8:
            return (x + f).astype(x.dtype), (kc, vc, kscale, vscale)
        return (x + f).astype(x.dtype), (kc, vc)

    xs = ((params["dec_layers"], cache["k"], cache["v"], cache["k_scale"],
           cache["v_scale"], cache["xk"], cache["xv"]) if paged and int8
          else (params["dec_layers"], cache["k"], cache["v"],
                cache["xk"], cache["xv"]))
    x, ys = layer_scan(cfg.scan_layers, body, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = griffin_linear(x[:, 0], params["head"])
    out = {"xk": cache["xk"], "xv": cache["xv"], "pos": pos}
    if paged and int8:
        out["k"], out["v"], out["k_scale"], out["v_scale"] = ys
    else:
        out["k"], out["v"] = ys
    if paged:
        out["pages"] = pages
    return logits, out
