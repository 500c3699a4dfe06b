"""Model registry: one uniform API over the four architecture families.

``build_model(cfg)`` returns a ``ModelApi`` whose members are pure functions
(params and caches are pytrees) — the runtime/launch layers jit and shard
them.  Analytic parameter/FLOP counts feed the roofline's MODEL_FLOPS.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, SHAPES, ShapeConfig
from . import rglru, transformer, whisper, xlstm
from .losses import chunked_cross_entropy

Params = Dict[str, Any]


@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    loss: Callable[..., jax.Array]            # (params, batch) -> scalar
    prefill: Callable[..., Any]               # (params, batch) -> (cache, logits)
    decode_step: Callable[..., Any]           # (params, cache, token, live=None) -> (logits, cache)
    init_cache: Callable[[int, int], Params]  # (batch, length) -> cache
    param_count: Callable[[], int]            # analytic, excludes embeddings
    param_count_total: Callable[[], int]
    # (cache, live) -> (2,) int32: per layer, the KV blocks the next decode
    # step's attention reads and the blocks its arena holds (None: no count)
    kv_blocks: Optional[Callable[..., jax.Array]] = None


def _transformer_api(cfg: ModelConfig) -> ModelApi:
    def loss(params, batch):
        hidden, aux = transformer.forward_hidden(cfg, params, batch["tokens"])
        ce = chunked_cross_entropy(hidden, transformer.unembed(cfg, params),
                                   batch["labels"], cfg.loss_chunk)
        return ce + 0.01 * aux

    def prefill_fn(params, batch, cache_len=None):
        return transformer.prefill(cfg, params, batch["tokens"], cache_len,
                                   lengths=batch.get("lengths"))

    return ModelApi(
        cfg=cfg,
        init=functools.partial(transformer.init_params, cfg),
        loss=loss,
        prefill=prefill_fn,
        decode_step=functools.partial(transformer.decode_step, cfg),
        init_cache=functools.partial(transformer.init_cache, cfg),
        param_count=lambda: _tf_param_count(cfg, active=True),
        param_count_total=lambda: _tf_param_count(cfg, active=False),
        kv_blocks=functools.partial(transformer.kv_blocks, cfg),
    )


def _tf_param_count(cfg: ModelConfig, active: bool) -> int:
    D, H, KVH, hd, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd,
                        cfg.d_ff)
    attn = D * H * hd + 2 * D * KVH * hd + H * hd * D
    if cfg.moe:
        E = cfg.moe.num_experts
        eff = cfg.moe.top_k if active else E
        ffn = D * E + eff * 3 * D * F
    else:
        ffn = (3 if cfg.gated_mlp else 2) * D * F
    return cfg.num_layers * (attn + ffn)


def _xlstm_api(cfg: ModelConfig) -> ModelApi:
    def loss(params, batch):
        hidden, aux = xlstm.forward_hidden(cfg, params, batch["tokens"])
        return chunked_cross_entropy(hidden, params["head"], batch["labels"],
                                     cfg.loss_chunk)

    def prefill_fn(params, batch, cache_len=None):
        return xlstm.prefill(cfg, params, batch["tokens"], cache_len,
                             lengths=batch.get("lengths"))

    def count(active=True):
        D = cfg.d_model
        din = int(cfg.proj_factor * D)
        H = cfg.num_heads
        hd_s = D // H
        pat = cfg.xlstm_pattern
        n_m = sum(1 for b in pat if b == "m") * (cfg.num_layers // len(pat))
        n_s = cfg.num_layers // len(pat) * (len(pat) - len(pat) + 1) \
            if False else (cfg.num_layers // len(pat)) * \
            sum(1 for b in pat if b == "s")
        m_p = D * 2 * din + 3 * H * (din // H) ** 2 + 2 * din * H + din * D
        s_p = 4 * (D * D + H * hd_s * hd_s) + D * int(4 * D / 3) * 2
        return n_m * m_p + n_s * s_p

    return ModelApi(
        cfg=cfg,
        init=functools.partial(xlstm.init_params, cfg),
        loss=loss,
        prefill=prefill_fn,
        decode_step=functools.partial(xlstm.decode_step, cfg),
        init_cache=functools.partial(xlstm.init_cache, cfg),
        param_count=lambda: count(),
        param_count_total=lambda: count(False),
    )


def _rglru_api(cfg: ModelConfig) -> ModelApi:
    def loss(params, batch):
        hidden, _ = rglru.forward_hidden(cfg, params, batch["tokens"])
        return chunked_cross_entropy(hidden, params["head"], batch["labels"],
                                     cfg.loss_chunk)

    def prefill_fn(params, batch, cache_len=None):
        return rglru.prefill(cfg, params, batch["tokens"], cache_len,
                             lengths=batch.get("lengths"))

    def count(active=True):
        D, F = cfg.d_model, cfg.d_ff
        R = cfg.lru_width or D
        H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        groups, tail = rglru._group_counts(cfg)
        rec = 2 * D * R + 2 * R * R + R * D
        attn = D * H * hd + 2 * D * KVH * hd + H * hd * D
        mlp = 3 * D * F
        return groups * (2 * rec + attn + 3 * mlp) + tail * (rec + mlp)

    return ModelApi(
        cfg=cfg,
        init=functools.partial(rglru.init_params, cfg),
        loss=loss,
        prefill=prefill_fn,
        decode_step=functools.partial(rglru.decode_step, cfg),
        init_cache=functools.partial(rglru.init_cache, cfg),
        param_count=lambda: count(),
        param_count_total=lambda: count(False),
    )


def _whisper_api(cfg: ModelConfig) -> ModelApi:
    def loss(params, batch):
        hidden, _ = whisper.forward_hidden(cfg, params, batch["tokens"],
                                           batch["frames"])
        return chunked_cross_entropy(hidden, params["head"], batch["labels"],
                                     cfg.loss_chunk)

    def prefill_fn(params, batch, cache_len=None):
        return whisper.prefill(cfg, params, batch["tokens"], batch["frames"],
                               cache_len, lengths=batch.get("lengths"))

    def count(active=True):
        D, H, hd, F = cfg.d_model, cfg.num_heads, cfg.hd, cfg.d_ff
        attn = 4 * D * H * hd
        mlp = 2 * D * F
        enc = cfg.encoder_layers * (attn + mlp)
        dec = cfg.num_layers * (2 * attn + mlp)
        return enc + dec

    return ModelApi(
        cfg=cfg,
        init=functools.partial(whisper.init_params, cfg),
        loss=loss,
        prefill=prefill_fn,
        decode_step=functools.partial(whisper.decode_step, cfg),
        init_cache=functools.partial(whisper.init_cache, cfg),
        param_count=lambda: count(),
        param_count_total=lambda: count(False),
    )


def build_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family in ("dense", "vlm", "moe"):
        return _transformer_api(cfg)
    if cfg.family == "ssm":
        return _xlstm_api(cfg)
    if cfg.family == "hybrid":
        return _rglru_api(cfg)
    if cfg.family == "audio":
        return _whisper_api(cfg)
    raise ValueError(f"unknown family {cfg.family}")


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell
    (weak-type-correct, shardable, no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    specs: Dict[str, Any] = {}
    if shape.kind == "train":
        specs = {"tokens": tok, "labels": tok}
    elif shape.kind == "prefill":
        specs = {"tokens": tok}
    else:                                    # decode: one new token
        specs = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    if cfg.is_encdec and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_frames, cfg.d_model), jnp.dtype(cfg.dtype))
    return specs
