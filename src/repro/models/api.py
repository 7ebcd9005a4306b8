"""Public model API: build any assigned architecture from its config.

``build_model(cfg)`` returns a ``Model`` bundling pure functions:
  init(key)                          -> params
  apply(params, batch, taps=None)    -> model outputs (family-specific)
  loss(params, batch)                -> scalar loss (train objective)
  prefill(params, batch, max_len[, lengths]) -> (logits, cache)
                                        (lengths: ragged right-padded prompts)
  decode_step(params, token, cache)  -> (logits, cache)
                                        (lm: ``count_lanes`` adds the
                                        MoE counters, ``lm_decode_step``)
  init_cache(batch, max_len)         -> empty cache (decode-only dry-runs)

``input_specs(cfg, shape)`` returns jax.ShapeDtypeStruct stand-ins for every
input of the step selected by the shape cell (train/prefill/decode) — used by
the multi-pod dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.models import vit as vit_mod

# stub frontend sizes (assignment: modality frontends provide precomputed
# embeddings; these are the prepended lengths used by the shape cells)
VLM_PATCHES = 1024
ENC_MEMORY_FOR_DECODE = 4096


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    apply: Callable
    loss: Callable
    prefill: Optional[Callable] = None
    decode_step: Optional[Callable] = None
    init_cache: Optional[Callable] = None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "lm":
        def apply_fn(params, batch, taps=None, train=False):
            return lm_mod.apply_lm(params, batch["tokens"], cfg, taps=taps,
                                   patch_embeds=batch.get("patch_embeds"),
                                   train=train)

        def prefill_fn(params, batch, max_len, lengths=None):
            return lm_mod.lm_prefill(params, batch["tokens"], cfg, max_len,
                                     patch_embeds=batch.get("patch_embeds"),
                                     lengths=lengths)

        return Model(
            cfg=cfg,
            init=lambda key: lm_mod.init_lm(key, cfg),
            apply=apply_fn,
            loss=lambda params, batch, train=True:
                lm_mod.lm_loss(params, batch, cfg, train=train),
            prefill=prefill_fn,
            decode_step=lambda params, token, cache, **kw:
                lm_mod.lm_decode_step(params, token, cache, cfg, **kw),
            init_cache=lambda batch, max_len:
                lm_mod.init_lm_cache(cfg, batch, max_len),
        )
    if cfg.family == "vit":
        def v_apply(params, batch, taps=None, train=False):
            inputs = batch.get("images", batch.get("embeds"))
            return vit_mod.apply_vit(params, inputs, cfg, taps=taps,
                                     train=train)
        return Model(
            cfg=cfg,
            init=lambda key: vit_mod.init_vit(key, cfg),
            apply=v_apply,
            loss=lambda params, batch, train=True:
                vit_mod.vit_loss(params, batch, cfg, train=train),
        )
    if cfg.family == "encdec":
        def e_apply(params, batch, taps=None, train=False):
            return encdec_mod.apply_encdec(params, batch["frames"],
                                           batch["tokens"], cfg, taps=taps,
                                           train=train)
        return Model(
            cfg=cfg,
            init=lambda key: encdec_mod.init_encdec(key, cfg),
            apply=e_apply,
            loss=lambda params, batch, train=True:
                encdec_mod.encdec_loss(params, batch, cfg, train=train),
            prefill=lambda params, batch, max_len, lengths=None:
                encdec_mod.encdec_prefill(params, batch["frames"],
                                          batch["tokens"], cfg, max_len,
                                          lengths=lengths),
            decode_step=lambda params, token, cache:
                encdec_mod.encdec_decode_step(params, token, cache, cfg),
        )
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Batch specs for the step the shape cell lowers.

    train  -> the loss/train_step batch
    prefill-> the prefill batch
    decode -> {'token': (B,1)} (cache specs come from cache_specs())
    """
    B, T = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    if cfg.family == "lm":
        if shape.kind == "train":
            batch = {"tokens": _sds((B, T), jnp.int32),
                     "labels": _sds((B, T), jnp.int32)}
            if cfg.frontend == "patch_stub":
                tt = T - VLM_PATCHES
                batch = {"tokens": _sds((B, tt), jnp.int32),
                         "labels": _sds((B, tt), jnp.int32),
                         "patch_embeds": _sds((B, VLM_PATCHES, cfg.d_model), dt)}
            return batch
        if shape.kind == "prefill":
            batch = {"tokens": _sds((B, T), jnp.int32)}
            if cfg.frontend == "patch_stub":
                batch = {"tokens": _sds((B, T - VLM_PATCHES), jnp.int32),
                         "patch_embeds": _sds((B, VLM_PATCHES, cfg.d_model), dt)}
            return batch
        return {"token": _sds((B, 1), jnp.int32)}
    if cfg.family == "encdec":
        if shape.kind == "train":
            return {"frames": _sds((B, T, cfg.d_model), dt),
                    "tokens": _sds((B, T), jnp.int32),
                    "labels": _sds((B, T), jnp.int32)}
        if shape.kind == "prefill":
            return {"frames": _sds((B, T, cfg.d_model), dt),
                    "tokens": _sds((B, T), jnp.int32)}
        return {"token": _sds((B, 1), jnp.int32)}
    if cfg.family == "vit":
        N = vit_mod.num_patches(cfg)
        if cfg.frontend == "patch_conv":
            return {"images": _sds((B, cfg.img_size, cfg.img_size, 3),
                                   jnp.float32),
                    "labels": _sds((B,), jnp.int32)}
        return {"embeds": _sds((B, N, cfg.d_model), dt),
                "labels": _sds((B,), jnp.int32)}
    raise ValueError(cfg.family)


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> Any:
    """ShapeDtypeStruct pytree for the decode cache at this shape cell."""
    assert shape.kind == "decode"
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "lm":
        return jax.eval_shape(
            lambda: lm_mod.init_lm_cache(cfg, B, S))
    if cfg.family == "encdec":
        model = build_model(cfg)
        params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        batch = {"frames": _sds((B, ENC_MEMORY_FOR_DECODE, cfg.d_model),
                                cfg.dtype),
                 "tokens": _sds((B, S), jnp.int32)}
        return jax.eval_shape(
            lambda p, fr, tk: model.prefill(
                p, {"frames": fr, "tokens": tk}, S)[1],
            params_sds, batch["frames"], batch["tokens"])
    raise ValueError(cfg.family)
