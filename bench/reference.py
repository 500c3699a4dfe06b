"""The comparison that decides ``correct``, and what every block's plain
reference shares.

Each configuration's forward pass is its block's ``logits_at``
(``bench.blocks``): plain float32 at ``HIGHEST`` precision, importing
nothing of the program.  ``quant=True`` is the control: the same
computation with every matmul operand rounded to float8 (e4m3, per-tensor
scale for weights, per-row for activations), the precision below the
configurations' bfloat16 (``bench.blocks.fp8``).

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

from . import blocks


def gap_of(ref, toks):
    """Per row: (best reference logit - reference logit of ``toks``) over
    the row's standard deviation."""
    picked = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
    return (ref.max(-1) - picked) / jnp.std(ref, axis=-1)


@functools.partial(jax.jit, static_argnames=("block", "arch_items",
                                             "control"))
def _gaps(w, tokens, idx, served, *, block, arch_items, control):
    logits_at = blocks.load(block).logits_at
    arch = dict(arch_items)
    ref = logits_at(w, tokens, idx, arch)
    if control:
        served = jnp.argmax(logits_at(w, tokens, idx, arch, quant=True), -1)
    return gap_of(ref, served)


def served_gaps(w, block: str, arch: dict, prompt, served, seq_len: int,
                n_max: int, control: bool = False) -> np.ndarray:
    """Gaps of the ``served`` tokens after ``prompt`` by the reference of
    the block named ``block`` (``control``: of the tokens the float8
    control puts first at the same positions).  The sequence is padded to
    ``seq_len`` and the positions to ``n_max``, so every request runs one
    compiled program."""
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
              block=block, arch_items=tuple(sorted(arch.items())),
              control=control)
    return np.asarray(g)[:n]
