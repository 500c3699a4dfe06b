"""Seeded model weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the same function feeds
the program under test and, after the window, the plain reference, so the
reference takes nothing the program made.  The tree has the program's
layout (its ``api.init`` shapes, read with ``jax.eval_shape``); the values
are the benchmark's own.

A configuration with ``weight_sparsity`` > 0 gets Sparse.B weights: in every
pruned GEMM a uniform choice of whole ``block_k x block_n`` blocks survives
and the rest is exactly zero.  The survivors are as many as the program's
block pruner keeps at that sparsity when it prunes in blocks of that size
(``unit`` = ``block_n``), so pruning these weights again zeroes nothing
more: the program serves exactly the matrices the reference multiplies by.
Which blocks survive is drawn from the configuration's ``mask_seed``, the
values from the run's seed.  So every seed serves the same block pattern,
with the uneven column tiles a pruner leaves, and the compacted shapes, and
every compiled program, are the same for every seed.

Which rule a leaf gets is its kind in the configuration's block
(``bench.blocks``, its ``LEAVES``): a leaf the block does not list has no
rule and is refused.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import blocks


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def block_plan(k: int, n: int, pruning: dict) -> dict:
    """Block grid of one pruned ``k x n`` matrix: block sizes, blocks per
    axis and how many blocks survive."""
    bk = min(pruning["block_k"], k)
    bn = min(pruning["block_n"], n)
    un = min(pruning["unit"], bn)
    if k % bk or n % bn or bn % un:
        raise ValueError(f"{k}x{n} does not tile into {bk}x{bn} blocks of "
                         f"{un}-wide units")
    nbk, nbn = k // bk, n // bn
    units = nbk * (n // un)
    keep_units = max(1, int(round(units * (1.0 - pruning["weight_sparsity"]))))
    kept = max(1, keep_units // (bn // un))
    return {"bk": bk, "bn": bn, "nbk": nbk, "nbn": nbn, "kept": kept}


def block_mask(key, shape, pruning: dict):
    """(..., nbk, nbn) bool: the blocks of a pruned ``shape`` that survive,
    ``kept`` of each matrix's blocks chosen uniformly."""
    *lead, k, n = shape
    p = block_plan(k, n, pruning)
    u = jax.random.uniform(key, (*lead, p["nbk"] * p["nbn"]))
    rank = jnp.argsort(jnp.argsort(u, axis=-1), axis=-1)
    return (rank < p["kept"]).reshape(*lead, p["nbk"], p["nbn"])


def _gemm(key, mkey, shape, dtype, pruning: Optional[dict]):
    *lead, k, n = shape
    w = jax.random.normal(key, shape, jnp.float32)
    if pruning is None:
        return (w / jnp.sqrt(k)).astype(dtype)
    p = block_plan(k, n, pruning)
    nbk, nbn = p["nbk"], p["nbn"]
    mask = block_mask(mkey, shape, pruning)
    w = w.reshape(*lead, nbk, p["bk"], nbn, p["bn"]) * \
        mask.reshape(*lead, nbk, 1, nbn, 1)
    scale = 1.0 / jnp.sqrt(k * p["kept"] / (nbk * nbn))  # output variance
    return (w.reshape(shape) * scale).astype(dtype)


def _pruning(name: str, conf: dict) -> Optional[dict]:
    pruning = conf["pruning"]
    if pruning["weight_sparsity"] > 0 and name in pruning["pruned"]:
        return pruning
    return None


def _leaf(key, mkey, name: str, kind, sd, conf: dict):
    """The leaf ``name`` by the rule of its ``kind``: ``norm`` (a norm's
    scale is 1 + w) and ``bias`` N(0, norm_std), ``embed`` N(0, embed_std),
    ``gemm`` N(0, 1/fan-in), block-pruned where the file prunes it."""
    init = conf["init"]
    if kind in ("norm", "bias"):
        return (init["norm_std"] * jax.random.normal(key, sd.shape)
                ).astype(sd.dtype)
    if kind == "embed":
        return (init["embed_std"] * jax.random.normal(key, sd.shape)
                ).astype(sd.dtype)
    if kind == "gemm":
        return _gemm(key, mkey, sd.shape, sd.dtype, _pruning(name, conf))
    raise ValueError(f"no rule for weight leaf {name!r} (kind {kind!r}) "
                     f"in block {blocks.name_of(conf)!r}")


def seed_words(seed: int):
    """A seed of any size as two uint32 words, passed as arguments so one
    compiled program serves every seed."""
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray((seed >> 32) & 0xFFFFFFFF, jnp.uint32))


def _mask_key(conf: dict, i: int):
    return jax.random.fold_in(
        jax.random.key(conf["pruning"].get("mask_seed", 0)), i)


def make(shapes, conf: dict, seed: int, shardings=None):
    """Weights of the tree ``shapes`` (ShapeDtypeStructs) from ``seed``,
    placed by ``shardings`` (default: the default device)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = blocks.of(conf).LEAVES

    def gen(lo, hi):
        key = jax.random.fold_in(jax.random.key(0), lo)
        key = jax.random.fold_in(key, hi)
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), _mask_key(conf, i),
                  leaf_name(path), kinds.get(leaf_name(path)), sd, conf)
            for i, (path, sd) in enumerate(flat)])

    return jax.jit(gen, out_shardings=shardings)(*seed_words(seed))


def masks(shapes, conf: dict) -> dict:
    """{leaf name: (..., nbk, nbn) bool} of every pruned leaf of ``shapes``:
    the block pattern :func:`make` gives every seed."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = blocks.of(conf).LEAVES
    out = {}
    for i, (path, sd) in enumerate(flat):
        name = leaf_name(path)
        pruning = _pruning(name, conf) if kinds.get(name) == "gemm" else None
        if pruning is not None:
            out[name] = block_mask(_mask_key(conf, i), sd.shape, pruning)
    return out
