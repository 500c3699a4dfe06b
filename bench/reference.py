"""Plain float32 reference of the decoder the configurations run, and the
comparison that decides ``correct``.

The reference imports nothing of the program.  It follows the block the
program serves for these configurations (its departures from the
published models are listed in PERF.md): token embedding; per layer an
RMS norm scaled by ``1 + w``, grouped-query attention with rotary
embeddings on each head's two halves, a causal softmax scaled by
``1/sqrt(head_dim)``, a residual add, an RMS norm and a SwiGLU MLP with a
second residual add; a final RMS norm and the output head.  Every matmul
runs at ``HIGHEST`` precision, every value in float32.

``quant=True`` is the control: the same computation with every matmul
operand rounded to float8 (e4m3, per-tensor scale for weights, per-row for
activations), the precision below the configurations' bfloat16.

What is compared: for each served token, how far its reference logit lies
below the reference's best logit at that position, in units of that
position's logit standard deviation.  A served greedy token that is the
reference's argmax reads 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                    # largest finite float8_e4m3fn


def fp8(x, axis=None):
    """Round ``x`` through float8 e4m3 with an amax scale over ``axis``
    (all axes when None)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


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


def gap_of(ref, toks):
    """Per row: (best reference logit - reference logit of ``toks``) over
    the row's standard deviation."""
    picked = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
    return (ref.max(-1) - picked) / jnp.std(ref, axis=-1)


@functools.partial(jax.jit, static_argnames=("arch_items", "control"))
def _gaps(w, tokens, idx, served, *, arch_items, control):
    arch = dict(arch_items)
    ref = logits_at(w, tokens, idx, arch)
    if control:
        served = jnp.argmax(logits_at(w, tokens, idx, arch, quant=True), -1)
    return gap_of(ref, served)


def served_gaps(w, arch: dict, prompt, served, seq_len: int, n_max: int,
                control: bool = False) -> np.ndarray:
    """Gaps of the ``served`` tokens after ``prompt`` (``control``: of the
    tokens the float8 control puts first at the same positions).  The
    sequence is padded to ``seq_len`` and the positions to ``n_max``, so
    every request runs one compiled program."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, p = len(served), len(prompt)
    seq = np.zeros(seq_len, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = served[:-1]
    idx = np.full(n_max, p - 1 + n - 1, np.int32)
    idx[:n] = p - 1 + np.arange(n)
    toks = np.zeros(n_max, np.int32)
    toks[:n] = served
    g = _gaps(w, jnp.asarray(seq), jnp.asarray(idx), jnp.asarray(toks),
              arch_items=tuple(sorted(arch.items())), control=control)
    return np.asarray(g)[:n]
