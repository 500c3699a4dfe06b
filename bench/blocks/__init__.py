"""One module per block form a configuration can serve, found by name.

A configuration file names its block under the top-level key ``"block"``;
where the key is absent the block is ``swiglu_decoder``.  A new block form
is a new module here, and the harness needs no other edit.  Each module
exports:

  ``LEAVES``      {weight leaf name: kind}, each kind one of ``norm``,
                  ``bias``, ``embed`` and ``gemm``, whose init rules
                  ``bench.weights`` keeps; a leaf it does not list gets no
                  rule, and ``bench.weights.make`` refuses it;
  ``logits_at(w, tokens, idx, arch, quant=False)``
                  the plain float32 forward pass at ``HIGHEST`` precision:
                  next-token logits (len(idx), vocab) of ``tokens`` at the
                  positions ``idx``; ``quant=True`` is the float8 control,
                  every matmul operand through ``fp8`` below;
  ``gemm_shapes(arch)``
                  (leaf name, k, n, count per decode step) of every weight
                  GEMM, which ``bench.work`` counts.

A block module imports nothing of the program, and of the harness only
this package (``HIGHEST``, ``fp8``).
"""
from __future__ import annotations

import importlib
import re

import jax
import jax.numpy as jnp

DEFAULT = "swiglu_decoder"
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                    # largest finite float8_e4m3fn


def fp8(x, axis=None):
    """Round ``x`` through float8 e4m3 with an amax scale over ``axis``
    (all axes when None)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def name_of(conf: dict) -> str:
    """The block a configuration file names."""
    return conf.get("block", DEFAULT)


def load(name: str):
    """The module of the block ``name``."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"block name {name!r} is no module name")
    return importlib.import_module(f"{__name__}.{name}")


def of(conf: dict):
    """The module of the block a configuration file names."""
    return load(name_of(conf))
