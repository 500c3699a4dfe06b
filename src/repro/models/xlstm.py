"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, chunkwise-parallel
for train, O(1)-state recurrent for decode) and sLSTM (scalar memory with
exponential gating and block-diagonal recurrence).

The 48 blocks follow the 7:1 mLSTM:sLSTM pattern, organized as
``lax.scan`` over groups of (7 stacked mLSTM + 1 sLSTM) so compile time is
depth-independent.  The Griffin sparse technique applies to the projection
GEMMs only (the recurrent state path is not a weight GEMM — DESIGN.md
Section 5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import (dense_init, griffin_linear, layer_scan, length_mask,
                     rms_norm, stack_layers, take_last)

Params = Dict[str, Any]
MIN_NORM = 1e-6


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def init_mlstm(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    din = int(cfg.proj_factor * D)
    ks = jax.random.split(key, 8)
    H = cfg.num_heads
    hd = din // H

    def blockdiag(k):
        # per-head projections (block-diagonal), as in the official xLSTM
        return (jax.random.normal(k, (H, hd, hd), jnp.float32) /
                jnp.sqrt(hd)).astype(dt)

    return {
        "ln": jnp.zeros((D,), dt),
        "w_up": dense_init(ks[0], D, 2 * din, dt),
        "wq": blockdiag(ks[1]),
        "wk": blockdiag(ks[2]),
        "wv": blockdiag(ks[3]),
        "wi": dense_init(ks[4], din, cfg.num_heads, dt),
        "wf": dense_init(ks[5], din, cfg.num_heads, dt),
        "gn": jnp.zeros((din,), dt),
        "w_down": dense_init(ks[6], din, D, dt),
    }


def _mlstm_chunk(q, k, v, i_pre, f_pre, state):
    """One chunk of stabilized chunkwise mLSTM.

    q,k,v: (B, L, H, hd) (k pre-scaled by 1/sqrt(hd));
    i_pre, f_pre: (B, L, H) gate pre-activations;
    state: (C (B,H,hd,hd), n (B,H,hd), m (B,H)).
    """
    C_prev, n_prev, m_prev = state
    B, L, H, hd = q.shape
    lf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))     # (B,L,H)
    b = jnp.cumsum(lf, axis=1)                             # inclusive
    total = b[:, -1]                                       # (B,H)
    i32 = i_pre.astype(jnp.float32)
    # intra-chunk log decay D[t,s] = b[t] - b[s] + i[s], s <= t
    Dlog = b[:, :, None, :] - b[:, None, :, :] + i32[:, None, :, :]
    tmask = jnp.tril(jnp.ones((L, L), bool))
    Dlog = jnp.where(tmask[None, :, :, None], Dlog, -jnp.inf)
    m_intra = Dlog.max(axis=2)                             # (B,L,H)
    a = m_prev[:, None, :] + b                             # inter decay (B,L,H)
    m_t = jnp.maximum(m_intra, a)
    qk = jnp.einsum("blhd,bshd->blsh", q.astype(jnp.float32),
                    k.astype(jnp.float32))
    P = jnp.exp(Dlog - m_t[:, :, None, :]) * qk
    h_intra = jnp.einsum("blsh,bshd->blhd", P, v.astype(jnp.float32))
    qn_intra = P.sum(axis=2)                               # (B,L,H)
    scale_inter = jnp.exp(a - m_t)                         # (B,L,H)
    h_inter = jnp.einsum("blhd,bhde->blhe", q.astype(jnp.float32), C_prev) * \
        scale_inter[..., None]
    qn_inter = jnp.einsum("blhd,bhd->blh", q.astype(jnp.float32), n_prev) * \
        scale_inter
    denom = jnp.maximum(jnp.abs(qn_intra + qn_inter), jnp.exp(-m_t)) + MIN_NORM
    h = (h_intra + h_inter) / denom[..., None]
    # state update to end of chunk
    w = total[:, None, :] - b + i32                        # (B,L,H)
    m_next = jnp.maximum(m_prev + total, w.max(axis=1))
    sc = jnp.exp(w - m_next[:, None, :])
    decay_old = jnp.exp(m_prev + total - m_next)           # (B,H)
    C_next = decay_old[:, :, None, None] * C_prev + \
        jnp.einsum("blh,blhd,blhe->bhde", sc, k.astype(jnp.float32),
                   v.astype(jnp.float32))
    n_next = decay_old[:, :, None] * n_prev + \
        jnp.einsum("blh,blhd->bhd", sc, k.astype(jnp.float32))
    return h, (C_next, n_next, m_next)


def mlstm_seq(cfg: ModelConfig, p: Params, x: jax.Array, state=None,
              chunk: int = 64, mask=None):
    """Full mLSTM block over a sequence.  x: (B, S, D).

    ``mask``: optional (B, S) validity mask of a right-padded batch
    (bucketed prefill).  Pad positions are made exact state no-ops through
    the gate pre-activations alone: the input gate is driven to -1e30 (its
    exp vanishes from both the intra-chunk decay matrix and the chunk state
    update) and the forget gate to +1e30 (log-sigmoid exactly 0, identity
    decay), so (C, n, m) after the padded sequence equal the state at the
    last real token."""
    B, S, D = x.shape
    H = cfg.num_heads
    din = int(cfg.proj_factor * D)
    hd = din // H
    h_in = rms_norm(x, p["ln"], cfg.norm_eps)
    up = griffin_linear(h_in, p["w_up"])
    xm, z = up[..., :din], up[..., din:]
    xh = xm.reshape(B, S, H, hd)
    q = jnp.einsum("bshd,hde->bshe", xh, p["wq"])
    k = jnp.einsum("bshd,hde->bshe", xh, p["wk"]) / \
        jnp.sqrt(hd).astype(x.dtype)
    v = jnp.einsum("bshd,hde->bshe", xh, p["wv"])
    i_pre = griffin_linear(xm, p["wi"])
    f_pre = griffin_linear(xm, p["wf"])
    if mask is not None:
        m3 = mask[:, :, None]
        i_pre = jnp.where(m3, i_pre, jnp.asarray(-1e30, i_pre.dtype))
        f_pre = jnp.where(m3, f_pre, jnp.asarray(1e30, f_pre.dtype))
    if state is None:
        state = mlstm_zero_state(cfg, B)
    L = min(chunk, S)
    nc = -(-S // L)
    assert nc * L == S, (S, L)

    def body(st, xs):
        qc, kc, vc, ic, fc = xs
        h, st = _mlstm_chunk(qc, kc, vc, ic, fc, st)
        return st, h

    xs = tuple(a.reshape(B, nc, L, *a.shape[2:]).swapaxes(0, 1)
               for a in (q, k, v, i_pre, f_pre))
    state, hs = jax.lax.scan(body, state, xs)
    h = hs.swapaxes(0, 1).reshape(B, S, H, hd).reshape(B, S, din)
    h = rms_norm(h, p["gn"], cfg.norm_eps)
    out = griffin_linear(
        h * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), p["w_down"])
    return (x + out).astype(x.dtype), state


def mlstm_zero_state(cfg: ModelConfig, batch: int):
    din = int(cfg.proj_factor * cfg.d_model)
    H = cfg.num_heads
    hd = din // H
    return (jnp.zeros((batch, H, hd, hd), jnp.float32),
            jnp.zeros((batch, H, hd), jnp.float32),
            jnp.full((batch, H), -1e30, jnp.float32))


def mlstm_step(cfg: ModelConfig, p: Params, x: jax.Array, state):
    """O(1) decode step.  x: (B, 1, D)."""
    out, state = mlstm_seq(cfg, p, x, state=state, chunk=1)
    return out, state


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def init_slstm(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    H = cfg.num_heads
    hd = D // H
    ks = jax.random.split(key, 10)
    def rmat(k):
        return (jax.random.normal(k, (H, hd, hd), jnp.float32) /
                jnp.sqrt(hd)).astype(dt)
    ff = int(4 * D / 3)
    return {
        "ln": jnp.zeros((D,), dt),
        "wz": dense_init(ks[0], D, D, dt), "rz": rmat(ks[1]),
        "wi": dense_init(ks[2], D, D, dt), "ri": rmat(ks[3]),
        "wf": dense_init(ks[4], D, D, dt), "rf": rmat(ks[5]),
        "wo": dense_init(ks[6], D, D, dt), "ro": rmat(ks[7]),
        "gn": jnp.zeros((D,), dt),
        "ln2": jnp.zeros((D,), dt),
        "w_ff1": dense_init(ks[8], D, ff, dt),
        "w_ff2": dense_init(ks[9], ff, D, dt),
    }


def slstm_zero_state(cfg: ModelConfig, batch: int):
    D, H = cfg.d_model, cfg.num_heads
    hd = D // H
    z = jnp.zeros((batch, H, hd), jnp.float32)
    return (z, z, z, jnp.full((batch, H, hd), -1e30, jnp.float32))


def slstm_seq(cfg: ModelConfig, p: Params, x: jax.Array, state=None,
              mask=None):
    """sLSTM block: strict recurrence over time (lax.scan).

    ``mask``: optional (B, S) validity mask of a right-padded batch
    (bucketed prefill).  The hidden state feeds back into the gates, so pad
    steps must hold the *entire* carried state — each step computes
    normally and then selects old-vs-new per row, leaving (c, n, h, m)
    after the padded sequence exactly the state at the last real token."""
    B, S, D = x.shape
    H = cfg.num_heads
    hd = D // H
    xin = rms_norm(x, p["ln"], cfg.norm_eps)
    # precompute input contributions for all gates: (B,S,H,hd)
    pre = {g: griffin_linear(xin, p["w" + g]).reshape(B, S, H, hd)
           .astype(jnp.float32) for g in ("z", "i", "f", "o")}
    if state is None:
        state = slstm_zero_state(cfg, B)
    R = {g: p["r" + g].astype(jnp.float32) for g in ("z", "i", "f", "o")}

    def step(st, xs):
        c, n, h, m = st
        if mask is None:
            zx, ix, fx, ox = xs                            # (B,H,hd)
        else:
            zx, ix, fx, ox, mt = xs
        rec = {g: jnp.einsum("bhd,hde->bhe", h, R[g])
               for g in ("z", "i", "f", "o")}
        zt = jnp.tanh(zx + rec["z"])
        it = ix + rec["i"]                                 # log-space
        ft = jax.nn.log_sigmoid(fx + rec["f"])
        ot = jax.nn.sigmoid(ox + rec["o"])
        m_new = jnp.maximum(ft + m, it)
        i_s = jnp.exp(it - m_new)
        f_s = jnp.exp(ft + m - m_new)
        c_new = f_s * c + i_s * zt
        n_new = f_s * n + i_s
        h_new = ot * c_new / jnp.maximum(n_new, MIN_NORM)
        if mask is not None:
            sel = mt[:, None, None]
            c_new = jnp.where(sel, c_new, c)
            n_new = jnp.where(sel, n_new, n)
            h_new = jnp.where(sel, h_new, h)
            m_new = jnp.where(sel, m_new, m)
        return (c_new, n_new, h_new, m_new), h_new

    xs = tuple(pre[g].swapaxes(0, 1) for g in ("z", "i", "f", "o"))
    if mask is not None:
        xs = xs + (mask.swapaxes(0, 1),)
    state, hs = jax.lax.scan(step, state, xs)
    h = hs.swapaxes(0, 1).reshape(B, S, D)
    h = rms_norm(h.astype(x.dtype), p["gn"], cfg.norm_eps)
    x = x + h
    f = rms_norm(x, p["ln2"], cfg.norm_eps)
    f = jax.nn.gelu(griffin_linear(f, p["w_ff1"]).astype(jnp.float32)
                    ).astype(x.dtype)
    return (x + griffin_linear(f, p["w_ff2"])).astype(x.dtype), state


# ---------------------------------------------------------------------------
# model assembly: scan over groups of (n_m mLSTM + n_s sLSTM)
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    pat = cfg.xlstm_pattern
    n_m = sum(1 for b in pat if b == "m")
    n_s = len(pat) - n_m
    groups = cfg.num_layers // len(pat)
    k_emb, k_m, k_s, k_h = jax.random.split(key, 4)

    def init_group_m(k):
        return stack_layers(functools.partial(init_mlstm, cfg), k, n_m)

    def init_group_s(k):
        return stack_layers(functools.partial(init_slstm, cfg), k, n_s)

    return {
        "embed": dense_init(k_emb, cfg.vocab_size, cfg.d_model, dt, scale=1.0),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
        "m_blocks": stack_layers(init_group_m, k_m, groups),   # (G, n_m, ...)
        "s_blocks": stack_layers(init_group_s, k_s, groups),   # (G, n_s, ...)
        "head": dense_init(k_h, cfg.d_model, cfg.vocab_size, dt),
    }


def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   chunk: int = 64):
    x = params["embed"][tokens]

    def group(x, gp):
        mp, sp = gp

        def m_body(x, lp):
            x, _ = mlstm_seq(cfg, lp, x, chunk=chunk)
            return x, None

        x, _ = layer_scan(cfg.scan_layers, m_body, x, mp)

        def s_body(x, lp):
            x, _ = slstm_seq(cfg, lp, x)
            return x, None

        x, _ = layer_scan(cfg.scan_layers, s_body, x, sp)
        return x, None

    fn = jax.checkpoint(group) if cfg.remat else group
    x, _ = layer_scan(cfg.scan_layers, fn, x,
                      (params["m_blocks"], params["s_blocks"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, length: int) -> Params:
    """Recurrent state: O(1) in sequence length — this is what makes
    long_500k decode feasible."""
    pat = cfg.xlstm_pattern
    n_m = sum(1 for b in pat if b == "m")
    n_s = len(pat) - n_m
    groups = cfg.num_layers // len(pat)

    def rep(x, *lead):
        return jnp.broadcast_to(x, tuple(lead) + x.shape)

    mC, mn, mm = mlstm_zero_state(cfg, batch)
    sc, sn, sh, sm = slstm_zero_state(cfg, batch)
    return {
        "mC": rep(mC, groups, n_m), "mn": rep(mn, groups, n_m),
        "mm": rep(mm, groups, n_m),
        "sc": rep(sc, groups, n_s), "sn": rep(sn, groups, n_s),
        "sh": rep(sh, groups, n_s), "sm": rep(sm, groups, n_s),
        "pos": jnp.zeros((), jnp.int32),
    }


def _scan_groups_with_state(cfg: ModelConfig, params, cache, x, chunk,
                            mask=None):
    def group(x, xs):
        (mp, sp, mC, mn, mm, sc, sn, sh, sm) = xs

        def m_body(x, ms):
            lp, C, n, m = ms
            x, (C, n, m) = mlstm_seq(cfg, lp, x, state=(C, n, m), chunk=chunk,
                                     mask=mask)
            return x, (C, n, m)

        x, mstate = jax.lax.scan(m_body, x, (mp, mC, mn, mm))

        def s_body(x, ss):
            lp, c, n, h, m = ss
            x, (c, n, h, m) = slstm_seq(cfg, lp, x, state=(c, n, h, m),
                                        mask=mask)
            return x, (c, n, h, m)

        x, sstate = jax.lax.scan(s_body, x, (sp, sc, sn, sh, sm))
        return x, mstate + sstate

    x, states = layer_scan(
        cfg.scan_layers, group, x,
        (params["m_blocks"], params["s_blocks"], cache["mC"],
                   cache["mn"], cache["mm"], cache["sc"], cache["sn"],
                   cache["sh"], cache["sm"]))
    new_cache = dict(zip(("mC", "mn", "mm", "sc", "sn", "sh", "sm"), states))
    return x, new_cache


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache_len=None, chunk: int = 64, lengths=None):
    """``lengths``: optional (B,) true prompt lengths of a right-padded
    batch (bucketed prefill).  Pad steps are exact state no-ops (see
    ``mlstm_seq`` / ``slstm_seq``), so the carried recurrent state equals
    the state at each row's last real token."""
    B, S = tokens.shape
    cache = init_cache(cfg, B, 0)
    x = params["embed"][tokens]
    mask = None if lengths is None else length_mask(lengths, S)
    x, new_cache = _scan_groups_with_state(cfg, params, cache, x, chunk,
                                           mask=mask)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        last, pos = x[:, -1], jnp.asarray(S - 1, jnp.int32)
    else:
        last = take_last(x, lengths)
        pos = (lengths - 1).astype(jnp.int32)          # per-row (B,) vector
    logits = griffin_linear(last, params["head"])
    new_cache["pos"] = pos
    return new_cache, logits


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, live=None):
    """One recurrent step.  The mLSTM/sLSTM state math is position-free, so
    per-slot serving (continuous batching, runtime/engine.py) needs no
    vector-position branch here: ``cache["pos"]`` increments elementwise
    whether it is the lockstep scalar or a (B,) per-slot vector.  ``live``
    (the rows whose logits are used) is not needed here."""
    x = params["embed"][token]
    x, new_cache = _scan_groups_with_state(cfg, params, cache, x, chunk=1)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = griffin_linear(x[:, 0], params["head"])
    new_cache["pos"] = cache["pos"] + 1
    return logits, new_cache
