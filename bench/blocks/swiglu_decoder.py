"""The decoder block stablelm-1.6b-b80 is served with (its departures from
the published model are listed in the configuration file and in PERF.md):
token embedding; per layer an RMS norm scaled by ``1 + w``, grouped-query
attention with rotary embeddings on each head's two halves, a causal
softmax scaled by ``1/sqrt(head_dim)``, a residual add, an RMS norm and a
SwiGLU MLP with a second residual add; a final RMS norm and an output head
not tied to the embedding.  Every matmul runs at ``HIGHEST`` precision,
every value in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import HIGHEST, fp8

LEAVES = {"embed": "embed", "ln1": "norm", "ln2": "norm",
          "final_norm": "norm", "wq": "gemm", "wk": "gemm", "wv": "gemm",
          "wo": "gemm", "w_gate": "gemm", "w_up": "gemm", "w_down": "gemm",
          "head": "gemm"}


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, theta):
    """x: (S, heads, hd); rotary over the two halves of each head."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits_at(w, tokens, idx, arch: dict, quant: bool = False):
    """Next-token logits (len(idx), vocab) of the sequence ``tokens`` at
    positions ``idx``."""
    if arch.get("act", "silu") != "silu":
        raise ValueError(f"swiglu_decoder gates with silu, not "
                         f"{arch['act']!r}")
    f32 = lambda a: a.astype(jnp.float32)
    qa = (lambda a: fp8(a, -1)) if quant else (lambda a: a)
    qw = fp8 if quant else (lambda a: a)
    mm = lambda a, b: jnp.dot(qa(a), qw(f32(b)), precision=HIGHEST)
    heads, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    s = tokens.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def layer(x, lp):
        h = _rms(x, f32(lp["ln1"]), eps)
        q = _rope(mm(h, lp["wq"]).reshape(s, heads, hd), theta)
        k = _rope(mm(h, lp["wk"]).reshape(s, kvh, hd), theta)
        v = mm(h, lp["wv"]).reshape(s, kvh, hd)
        qg = qa(q).reshape(s, kvh, heads // kvh, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", qg, qa(k), precision=HIGHEST)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", qa(p), qa(v), precision=HIGHEST)
        x = x + mm(o.reshape(s, heads * hd), lp["wo"])
        h2 = _rms(x, f32(lp["ln2"]), eps)
        f = jax.nn.silu(mm(h2, lp["w_gate"])) * mm(h2, lp["w_up"])
        return x + mm(f, lp["w_down"]), None

    x = f32(w["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, f32(w["final_norm"]), eps)
    return mm(x[idx], w["head"])


def gemm_shapes(arch: dict):
    """(name, k, n, count per step) of every weight GEMM of a decode step."""
    d, h, kvh, hd = (arch["d_model"], arch["num_heads"],
                     arch["num_kv_heads"], arch["head_dim"])
    f, L = arch["d_ff"], arch["num_layers"]
    return [("wq", d, h * hd, L), ("wk", d, kvh * hd, L),
            ("wv", d, kvh * hd, L), ("wo", h * hd, d, L),
            ("w_gate", d, f, L), ("w_up", d, f, L), ("w_down", f, d, L),
            ("head", d, arch["vocab_size"], 1)]
