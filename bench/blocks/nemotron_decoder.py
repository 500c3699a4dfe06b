"""The decoder block minitron-8b-dense is served with: Nemotron-4's block
as Minitron-8B publishes it (hf:nvidia/Minitron-8B-Base ``config.json``,
``model_type: nemotron``; arXiv:2407.14679).  Token embedding; per layer a
LayerNorm with scale ``1 + w`` and a bias (``NemotronLayerNorm1P``),
grouped-query attention with rotary embeddings on the first
``rotary_frac`` of each head's channels (frequencies over that width, the
two halves of it rotated; the rest pass through), a causal softmax scaled
by ``1/sqrt(head_dim)``, a residual add, a second LayerNorm1p and an
ungated MLP ``w_down(relu(w_up h)^2)`` with a second residual add; a
final LayerNorm1p and an output head not tied to the embedding.  No
biases on attention or MLP.  Every matmul runs at ``HIGHEST`` precision,
every value in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import HIGHEST, fp8

LEAVES = {"embed": "embed", "ln1": "norm", "ln1_b": "bias", "ln2": "norm",
          "ln2_b": "bias", "final_norm": "norm", "final_norm_b": "bias",
          "wq": "gemm", "wk": "gemm", "wv": "gemm", "wo": "gemm",
          "w_up": "gemm", "w_down": "gemm", "head": "gemm"}

# the arch keys that name this block's mechanisms, as the file must state them
MECHANISMS = {"norm": "layernorm1p", "gated_mlp": False, "act": "relu2"}


def _ln1p(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w) + b


def _rope(x, theta, frac):
    """x: (S, heads, hd); rotary over the first ``frac * hd`` channels."""
    s, _, hd = x.shape
    rot = int(hd * frac)
    half = rot // 2
    freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def logits_at(w, tokens, idx, arch: dict, quant: bool = False):
    """Next-token logits (len(idx), vocab) of the sequence ``tokens`` at
    positions ``idx``."""
    for k, v in MECHANISMS.items():
        if arch.get(k) != v:
            raise ValueError(f"nemotron_decoder needs {k}={v!r}, the "
                             f"configuration has {arch.get(k)!r}")
    f32 = lambda a: a.astype(jnp.float32)
    qa = (lambda a: fp8(a, -1)) if quant else (lambda a: a)
    qw = fp8 if quant else (lambda a: a)
    mm = lambda a, b: jnp.dot(qa(a), qw(f32(b)), precision=HIGHEST)
    heads, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    frac = arch["rotary_frac"]
    s = tokens.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def layer(x, lp):
        h = _ln1p(x, f32(lp["ln1"]), f32(lp["ln1_b"]), eps)
        q = _rope(mm(h, lp["wq"]).reshape(s, heads, hd), theta, frac)
        k = _rope(mm(h, lp["wk"]).reshape(s, kvh, hd), theta, frac)
        v = mm(h, lp["wv"]).reshape(s, kvh, hd)
        qg = qa(q).reshape(s, kvh, heads // kvh, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", qg, qa(k), precision=HIGHEST)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", qa(p), qa(v), precision=HIGHEST)
        x = x + mm(o.reshape(s, heads * hd), lp["wo"])
        h2 = _ln1p(x, f32(lp["ln2"]), f32(lp["ln2_b"]), eps)
        f = jnp.square(jax.nn.relu(mm(h2, lp["w_up"])))
        return x + mm(f, lp["w_down"]), None

    x = f32(w["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _ln1p(x, f32(w["final_norm"]), f32(w["final_norm_b"]), eps)
    return mm(x[idx], w["head"])


def gemm_shapes(arch: dict):
    """(name, k, n, count per step) of every weight GEMM of a decode step."""
    d, h, kvh, hd = (arch["d_model"], arch["num_heads"],
                     arch["num_kv_heads"], arch["head_dim"])
    f, L = arch["d_ff"], arch["num_layers"]
    return [("wq", d, h * hd, L), ("wk", d, kvh * hd, L),
            ("wv", d, kvh * hd, L), ("wo", h * hd, d, L),
            ("w_up", d, f, L), ("w_down", f, d, L),
            ("head", d, arch["vocab_size"], 1)]
