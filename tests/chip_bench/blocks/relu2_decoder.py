"""A block the harness knows from this file alone, for the tests: per layer
a LayerNorm1p with a bias (scale ``1 + w``), multi-head attention with no
rotary, a residual add, a second LayerNorm1p and an MLP with no gate (up,
squared ReLU, down) with a second residual add; a final LayerNorm1p and
the output head."""
import jax
import jax.numpy as jnp

from bench.blocks import HIGHEST, fp8

LEAVES = {"embed": "embed", "ln1": "norm", "ln1_b": "bias", "ln2": "norm",
          "ln2_b": "bias", "final_norm": "norm", "final_norm_b": "bias",
          "wq": "gemm", "wk": "gemm", "wv": "gemm", "wo": "gemm",
          "w_up": "gemm", "w_down": "gemm", "head": "gemm"}


def _ln1p(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w) + b


def logits_at(w, tokens, idx, arch, quant=False):
    f32 = lambda a: a.astype(jnp.float32)
    qa = (lambda a: fp8(a, -1)) if quant else (lambda a: a)
    qw = fp8 if quant else (lambda a: a)
    mm = lambda a, b: jnp.dot(qa(a), qw(f32(b)), precision=HIGHEST)
    heads, hd, eps = arch["num_heads"], arch["head_dim"], arch["norm_eps"]
    s = tokens.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def layer(x, lp):
        h = _ln1p(x, f32(lp["ln1"]), f32(lp["ln1_b"]), eps)
        q, k, v = (mm(h, lp[n]).reshape(s, heads, hd)
                   for n in ("wq", "wk", "wv"))
        sc = jnp.einsum("qhd,shd->hqs", qa(q), qa(k), precision=HIGHEST)
        sc = jnp.where(causal, sc / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqs,shd->qhd", qa(p), qa(v), precision=HIGHEST)
        x = x + mm(o.reshape(s, heads * hd), lp["wo"])
        h2 = _ln1p(x, f32(lp["ln2"]), f32(lp["ln2_b"]), eps)
        return x + mm(jnp.square(jax.nn.relu(mm(h2, lp["w_up"]))),
                      lp["w_down"]), None

    x = f32(w["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _ln1p(x, f32(w["final_norm"]), f32(w["final_norm_b"]), eps)
    return mm(x[idx], w["head"])


def gemm_shapes(arch):
    d, hw = arch["d_model"], arch["num_heads"] * arch["head_dim"]
    f, L = arch["d_ff"], arch["num_layers"]
    return [("wq", d, hw, L), ("wk", d, hw, L), ("wv", d, hw, L),
            ("wo", hw, d, L), ("w_up", d, f, L), ("w_down", f, d, L),
            ("head", d, arch["vocab_size"], 1)]
