"""RecurrentGemma [arXiv:2402.19427]: RG-LRU recurrent blocks + local
attention (MQA, window 2048) in a (rec, rec, attn) pattern, GeGLU MLPs.

The RG-LRU diagonal linear recurrence is evaluated with
``lax.associative_scan`` (log-depth, fully counted by cost analysis); decode
carries O(1) recurrent + conv state plus a rolling window cache for the
attention layers, which is what makes long_500k decode O(window).

Every weight GEMM goes through ``models.common.griffin_linear``, like the
other families (DESIGN.md Section 4): plain ``x @ w`` outside a
``sparse_execution`` scope, kernel/mesh dispatch inside one.  This is
what lets block-pruned hybrid weights execute (``sparsity.sparsify_params``
already selected rglru's attention/MLP names) and what makes the family
mesh-servable: the SPMD scope's replication constraints live in
``griffin_linear``, and without them GSPMD is free to leave ``k``/``q``
sharded across the rope half-split — a miscompile-prone layout on the
emulated CPU mesh (DESIGN.md Section 10).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import decode_attention, local_attention
from .common import (act_fn, dense_init, griffin_linear, layer_scan,
                     length_mask, paged_view, paged_write, rms_norm, rope,
                     stack_layers, take_last, write_kv_slot)

Params = Dict[str, Any]
LRU_C = 8.0


# ---------------------------------------------------------------------------
# RG-LRU + conv
# ---------------------------------------------------------------------------

def init_rec_block(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    R = cfg.lru_width or D
    ks = jax.random.split(key, 8)
    return {
        "ln": jnp.zeros((D,), dt),
        "w_x": dense_init(ks[0], D, R, dt),
        "w_gate": dense_init(ks[1], D, R, dt),
        "conv": (jax.random.normal(ks[2], (cfg.conv_width, R), jnp.float32)
                 * 0.1).astype(dt),
        "w_rg": dense_init(ks[3], R, R, dt),       # recurrence gate
        "w_ig": dense_init(ks[4], R, R, dt),       # input gate
        "lam": jnp.linspace(0.9, 5.0, R).astype(jnp.float32),  # softplus param
        "w_out": dense_init(ks[5], R, D, dt),
    }


def _causal_conv(x: jax.Array, w: jax.Array, state=None, lengths=None):
    """Depthwise causal conv along time.  x: (B,S,R), w: (cw,R).
    state: (B, cw-1, R) previous inputs for decode.

    ``lengths``: optional (B,) true lengths of a right-padded batch
    (bucketed prefill).  The conv is causal, so real outputs never see the
    pads — but the carried decode state must be the last ``cw-1`` *real*
    inputs, which sit at positions ``length-cw+1..length-1`` rather than at
    the array tail; they are gathered per row."""
    cw = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(cw))
    if cw == 1:
        new_state = None
    elif lengths is None:
        new_state = xp[:, -(cw - 1):]
    else:
        # xp index of input position p is p + cw - 1 (left pad); want
        # positions length-cw+1..length-1 -> xp indices length..length+cw-2
        idx = lengths[:, None] + jnp.arange(cw - 1)[None, :]
        new_state = jnp.take_along_axis(xp, idx[..., None], axis=1)
    return out.astype(x.dtype), new_state


def _rg_lru(x: jax.Array, p: Params, h0=None, mask=None):
    """x: (B,S,R) -> (B,S,R), h_last.  Diagonal gated linear recurrence:
      log a_t = -c * softplus(lam) * sigmoid(x W_rg)
      h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(x W_ig) * x_t)
    evaluated as an associative scan on (a, b) pairs.

    ``mask``: optional (B, S) validity mask of a right-padded batch
    (bucketed prefill): pad steps run with (a, b) = (1, 0) — an exact
    identity — so ``h_last`` is the state at each row's last real token."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(griffin_linear(xf, p["w_rg"].astype(jnp.float32)))
    i = jax.nn.sigmoid(griffin_linear(xf, p["w_ig"].astype(jnp.float32)))
    log_a = -LRU_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    if mask is not None:
        m3 = mask[:, :, None]
        a = jnp.where(m3, a, 1.0)
        b = jnp.where(m3, b, 0.0)
    if h0 is not None:
        # fold the carried state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(l, r_):
        a1, b1 = l
        a2, b2 = r_
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rec_mix(cfg: ModelConfig, p: Params, x: jax.Array, state=None,
            mask=None, lengths=None):
    """Recurrent mixing block.  state: (h0 (B,R) f32, conv (B,cw-1,R)).
    ``mask``/``lengths`` describe right padding (bucketed prefill)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xr = griffin_linear(h, p["w_x"])
    gate = jax.nn.gelu(griffin_linear(h, p["w_gate"])
                       .astype(jnp.float32)).astype(x.dtype)
    h0, conv_state = (None, None) if state is None else state
    xr, new_conv = _causal_conv(xr, p["conv"], conv_state, lengths=lengths)
    hr, h_last = _rg_lru(xr, p, h0, mask=mask)
    out = griffin_linear(hr * gate, p["w_out"])
    return (x + out).astype(x.dtype), (h_last, new_conv)


# ---------------------------------------------------------------------------
# attention + MLP blocks
# ---------------------------------------------------------------------------

def init_attn_block(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    D, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.zeros((D,), dt),
        "wq": dense_init(ks[0], D, H * hd, dt),
        "wk": dense_init(ks[1], D, KVH * hd, dt),
        "wv": dense_init(ks[2], D, KVH * hd, dt),
        "wo": dense_init(ks[3], H * hd, D, dt),
    }


def init_mlp(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.zeros((cfg.d_model,), dt),
        "w_gate": dense_init(ks[0], cfg.d_model, cfg.d_ff, dt),
        "w_up": dense_init(ks[1], cfg.d_model, cfg.d_ff, dt),
        "w_down": dense_init(ks[2], cfg.d_ff, cfg.d_model, dt),
    }


def mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    f = act_fn(cfg.act)(griffin_linear(h, p["w_gate"])) * \
        griffin_linear(h, p["w_up"])
    return (x + griffin_linear(f, p["w_down"])).astype(x.dtype)


def attn_mix(cfg: ModelConfig, p: Params, x: jax.Array, positions):
    B, S, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = rope(griffin_linear(h, p["wq"]).reshape(B, S, H, hd), positions,
             cfg.rope_theta)
    k = rope(griffin_linear(h, p["wk"]).reshape(B, S, KVH, hd), positions,
             cfg.rope_theta)
    v = griffin_linear(h, p["wv"]).reshape(B, S, KVH, hd)
    o = local_attention(q, k, v, window=cfg.window,
                        q_chunk=min(cfg.kv_chunk, cfg.window))
    return (x + griffin_linear(o.reshape(B, S, -1), p["wo"])
            ).astype(x.dtype), (k, v)


def attn_decode(cfg: ModelConfig, p: Params, x: jax.Array, kc, vc, pos):
    """One-token local attention against a rolling window cache.  ``pos``
    is a scalar, or a (B,) vector of per-row positions (continuous-batching
    slot pools, runtime/engine.py)."""
    B = x.shape[0]
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    per_slot = pos.ndim > 0
    posv = pos[:, None] if per_slot else pos[None]
    q = rope(griffin_linear(h, p["wq"]).reshape(B, 1, H, hd), posv,
             cfg.rope_theta)
    k = rope(griffin_linear(h, p["wk"]).reshape(B, 1, KVH, hd), posv,
             cfg.rope_theta)
    v = griffin_linear(h, p["wv"]).reshape(B, 1, KVH, hd)
    clen = kc.shape[1]
    slot = pos % clen
    kc = write_kv_slot(kc, k, slot)
    vc = write_kv_slot(vc, v, slot)
    eff = jnp.minimum(pos, clen - 1)
    o = decode_attention(q, kc, vc, eff, window=None)
    return (x + griffin_linear(o.reshape(B, 1, -1), p["wo"])
            ).astype(x.dtype), kc, vc


def attn_decode_paged(cfg: ModelConfig, p: Params, x: jax.Array, kc, vc,
                      kscale, vscale, pages, pos):
    """Paged twin of :func:`attn_decode` (runtime/paging.py).  Paging only
    activates when ``window >= cache_len`` (discovery rule), where the
    rolling slot/eff-pos algebra of the fixed path reduces for live rows to
    write-at-``pos`` / attend-to-``pos`` — bit-identical on the gathered
    view.  ``kscale``/``vscale`` are None for fp32 pools."""
    B = x.shape[0]
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    per_slot = pos.ndim > 0
    posv = pos[:, None] if per_slot else pos[None]
    q = rope(griffin_linear(h, p["wq"]).reshape(B, 1, H, hd), posv,
             cfg.rope_theta)
    k = rope(griffin_linear(h, p["wk"]).reshape(B, 1, KVH, hd), posv,
             cfg.rope_theta)
    v = griffin_linear(h, p["wv"]).reshape(B, 1, KVH, hd)
    page_size = kc.shape[1]
    kc, kscale = paged_write(kc, kscale, pages, k, pos, page_size)
    vc, vscale = paged_write(vc, vscale, pages, v, pos, page_size)
    o = decode_attention(q, paged_view(kc, kscale, pages, x.dtype),
                         paged_view(vc, vscale, pages, x.dtype), pos,
                         window=None)
    return (x + griffin_linear(o.reshape(B, 1, -1), p["wo"])
            ).astype(x.dtype), kc, vc, kscale, vscale


# ---------------------------------------------------------------------------
# model assembly: scan over (rec, rec, attn) groups + rec tail
# ---------------------------------------------------------------------------

def _group_counts(cfg: ModelConfig) -> Tuple[int, int]:
    plen = len(cfg.block_pattern)          # 3
    groups = cfg.num_layers // plen        # 12
    tail = cfg.num_layers - groups * plen  # 2 (rec, rec)
    return groups, tail


def init_params(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    groups, tail = _group_counts(cfg)
    ks = jax.random.split(key, 6)

    def init_group(k):
        kk = jax.random.split(k, 6)
        return {
            "rec1": init_rec_block(cfg, kk[0]), "mlp1": init_mlp(cfg, kk[1]),
            "rec2": init_rec_block(cfg, kk[2]), "mlp2": init_mlp(cfg, kk[3]),
            "attn": init_attn_block(cfg, kk[4]), "mlp3": init_mlp(cfg, kk[5]),
        }

    def init_tail(k):
        kk = jax.random.split(k, 2)
        return {"rec": init_rec_block(cfg, kk[0]), "mlp": init_mlp(cfg, kk[1])}

    return {
        "embed": dense_init(ks[0], cfg.vocab_size, cfg.d_model, dt, scale=1.0),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
        "groups": stack_layers(init_group, ks[1], groups),
        "tail": stack_layers(init_tail, ks[2], tail),
        "head": dense_init(ks[3], cfg.d_model, cfg.vocab_size, dt),
    }


def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array):
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1])

    def group(x, gp):
        x, _ = rec_mix(cfg, gp["rec1"], x)
        x = mlp(cfg, gp["mlp1"], x)
        x, _ = rec_mix(cfg, gp["rec2"], x)
        x = mlp(cfg, gp["mlp2"], x)
        x, _ = attn_mix(cfg, gp["attn"], x, positions)
        x = mlp(cfg, gp["mlp3"], x)
        return x, None

    def tail(x, tp):
        x, _ = rec_mix(cfg, tp["rec"], x)
        x = mlp(cfg, tp["mlp"], x)
        return x, None

    gfn = jax.checkpoint(group) if cfg.remat else group
    x, _ = layer_scan(cfg.scan_layers, gfn, x, params["groups"])
    x, _ = layer_scan(cfg.scan_layers, tail, x, params["tail"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, length: int) -> Params:
    groups, tail = _group_counts(cfg)
    R = cfg.lru_width or cfg.d_model
    cw = cfg.conv_width
    clen = min(length, cfg.window)
    dt = jnp.dtype(cfg.dtype)
    z_h = jnp.zeros((groups, 2, batch, R), jnp.float32)
    z_conv = jnp.zeros((groups, 2, batch, cw - 1, R), dt)
    return {
        "rec_h": z_h, "rec_conv": z_conv,
        "tail_h": jnp.zeros((tail, batch, R), jnp.float32),
        "tail_conv": jnp.zeros((tail, batch, cw - 1, R), dt),
        "k": jnp.zeros((groups, batch, clen, cfg.num_kv_heads, cfg.hd), dt),
        "v": jnp.zeros((groups, batch, clen, cfg.num_kv_heads, cfg.hd), dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache_len=None, lengths=None):
    """``lengths``: optional (B,) true prompt lengths of a right-padded
    batch (bucketed prefill).  Local attention is causal (real positions
    never see pads); the recurrent/conv state updates are masked to
    identity at pads; pad K/V rows sit in slots ``length..S-1`` where the
    decode loop overwrites slot ``pos % clen`` before its position mask
    admits it (requires the padded length to fit the window cache — the
    bucket policy in runtime/engine.py clamps to it)."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    clen = min(cache_len or S, cfg.window)
    mask = None if lengths is None else length_mask(lengths, S)
    if lengths is not None:
        assert S <= clen, "bucketed prefill must fit the window cache"

    def group(x, gp):
        x, st1 = rec_mix(cfg, gp["rec1"], x, mask=mask, lengths=lengths)
        x = mlp(cfg, gp["mlp1"], x)
        x, st2 = rec_mix(cfg, gp["rec2"], x, mask=mask, lengths=lengths)
        x = mlp(cfg, gp["mlp2"], x)
        x, (k, v) = attn_mix(cfg, gp["attn"], x, positions)
        x = mlp(cfg, gp["mlp3"], x)
        # keep the last window of K/V, rolled so decode can continue writing
        k, v = k[:, -clen:], v[:, -clen:]
        return x, (jnp.stack([st1[0], st2[0]]),
                   jnp.stack([st1[1], st2[1]]), k, v)

    def tail(x, tp):
        x, st = rec_mix(cfg, tp["rec"], x, mask=mask, lengths=lengths)
        x = mlp(cfg, tp["mlp"], x)
        return x, st

    x = params["embed"][tokens]
    x, (rec_h, rec_conv, ks, vs) = layer_scan(cfg.scan_layers, group, x,
                                              params["groups"])
    x, (tail_h, tail_conv) = layer_scan(cfg.scan_layers, tail, x,
                                        params["tail"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        last, pos = x[:, -1], jnp.asarray(S - 1, jnp.int32)
    else:
        last = take_last(x, lengths)
        pos = (lengths - 1).astype(jnp.int32)          # per-row (B,) vector
    logits = griffin_linear(last, params["head"])
    # roll the window cache so that slot (pos % clen) is consistent; short
    # prompts pad the tail so the cache is always exactly clen long — the
    # arena shape init_cache declares (decode writes slots S, S+1, ... and
    # the eff-pos mask hides the padding, exactly as in models/transformer)
    if ks.shape[2] < clen:
        pad = clen - ks.shape[2]
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    shift = (S % clen) if S >= clen else 0
    ks = jnp.roll(ks, shift, axis=2)
    vs = jnp.roll(vs, shift, axis=2)
    cache = {"rec_h": rec_h, "rec_conv": rec_conv, "tail_h": tail_h,
             "tail_conv": tail_conv, "k": ks, "v": vs, "pos": pos}
    return cache, logits


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, live=None):
    """One decode step.  ``live`` (the rows whose logits are used) is not
    needed here."""
    x = params["embed"][token]
    pos = cache["pos"] + 1
    # "pages" marks a paged attention cache (runtime/paging.py): k/v become
    # (groups, num_pages, page_size, KVH, hd) pools indexed through the slot
    # page table; the recurrent/conv state leaves are untouched.
    paged = "pages" in cache
    pages = cache.get("pages")
    int8 = "k_scale" in cache

    def group(x, xs):
        if paged and int8:
            gp, rh, rconv, kc, vc, ksc, vsc = xs
        else:
            gp, rh, rconv, kc, vc = xs
            ksc = vsc = None
        x, st1 = rec_mix(cfg, gp["rec1"], x, state=(rh[0], rconv[0]))
        x = mlp(cfg, gp["mlp1"], x)
        x, st2 = rec_mix(cfg, gp["rec2"], x, state=(rh[1], rconv[1]))
        x = mlp(cfg, gp["mlp2"], x)
        if paged:
            x, kc, vc, ksc, vsc = attn_decode_paged(
                cfg, gp["attn"], x, kc, vc, ksc, vsc, pages, pos)
        else:
            x, kc, vc = attn_decode(cfg, gp["attn"], x, kc, vc, pos)
        x = mlp(cfg, gp["mlp3"], x)
        st = (jnp.stack([st1[0], st2[0]]), jnp.stack([st1[1], st2[1]]))
        return x, (st + (kc, vc, ksc, vsc) if paged and int8
                   else st + (kc, vc))

    def tail(x, xs):
        tp, rh, rconv = xs
        x, st = rec_mix(cfg, tp["rec"], x, state=(rh, rconv))
        x = mlp(cfg, tp["mlp"], x)
        return x, st

    xs = ((params["groups"], cache["rec_h"], cache["rec_conv"],
           cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
          if paged and int8
          else (params["groups"], cache["rec_h"], cache["rec_conv"],
                cache["k"], cache["v"]))
    x, ys = layer_scan(cfg.scan_layers, group, x, xs)
    x, (tail_h, tail_conv) = layer_scan(
        cfg.scan_layers, tail, x,
        (params["tail"], cache["tail_h"], cache["tail_conv"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = griffin_linear(x[:, 0], params["head"])
    out = {"tail_h": tail_h, "tail_conv": tail_conv, "pos": pos}
    if paged and int8:
        (out["rec_h"], out["rec_conv"], out["k"], out["v"],
         out["k_scale"], out["v_scale"]) = ys
    else:
        out["rec_h"], out["rec_conv"], out["k"], out["v"] = ys
    if paged:
        out["pages"] = pages
    return logits, out
