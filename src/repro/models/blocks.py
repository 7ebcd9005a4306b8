"""Transformer block assembly: (norm -> mixer -> norm -> mlp) per layer spec.

A block's mixer is one of 'attn' | 'swa' | 'mamba' | 'rwkv'; its MLP is dense
or MoE (per ``cfg.layer_is_moe``). Decoder blocks in enc-dec models carry an
extra cross-attention sub-layer.

The two halves run under ``jax.named_scope``: the mixer half (norm, mixer,
residual) under ``attn`` for attention mixers and under the mixer's kind
otherwise, the MLP half under ``mlp``. The names ride in every op's
metadata, so a profile splits a layer's device time between them
(``repro.spans.NAMES``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_norm, init_norm, merge_taps


def init_block(key, cfg, kind: str, is_moe: bool, *, cross: bool = False,
               dense_ff: int | None = None):
    ks = jax.random.split(key, 5)
    p = {"ln1": init_norm(ks[0], cfg)}
    if kind in ("attn", "swa"):
        p["mixer"] = attn_mod.init_attn(ks[1], cfg, kind)
    elif kind == "mamba":
        p["mixer"] = ssm_mod.init_mamba(ks[1], cfg)
    elif kind == "rwkv":
        p["mixer"] = ssm_mod.init_rwkv_time(ks[1], cfg)
    else:
        raise ValueError(kind)
    p["ln2"] = init_norm(ks[2], cfg)
    if kind == "rwkv":
        p["mlp"] = ssm_mod.init_rwkv_channel(ks[3], cfg)
    elif is_moe:
        p["mlp"] = mlp_mod.init_moe(ks[3], cfg)
    else:
        if dense_ff is not None:
            p["mlp"] = mlp_mod.init_mlp(ks[3], cfg, d_ff=dense_ff)
        else:
            p["mlp"] = mlp_mod.init_mlp(ks[3], cfg)
    if cross:
        kc = jax.random.split(ks[4], 2)
        p["ln_cross"] = init_norm(kc[0], cfg)
        p["cross"] = attn_mod.init_attn(kc[1], cfg, "attn", cross=True)
    return p


def _mixer_scope(kind: str) -> str:
    return "attn" if kind in ("attn", "swa") else kind


def apply_block(p, x, cfg, kind: str, is_moe: bool, *, positions,
                taps=None, mem=None, mask_kind="causal", train=False):
    """Full-sequence block. Returns (x, aux_loss)."""
    t = {} if taps is not None else None
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(_mixer_scope(kind)):
        h = apply_norm(p["ln1"], x, cfg)
        if kind in ("attn", "swa"):
            y, _ = attn_mod.apply_attn(p["mixer"], h, cfg, kind,
                                       positions=positions, taps=t,
                                       mask_kind=mask_kind)
        elif kind == "mamba":
            y, _ = ssm_mod.apply_mamba(p["mixer"], h, cfg, taps=t)
        else:  # rwkv
            y, _ = ssm_mod.apply_rwkv_time(p["mixer"], h, cfg, taps=t)
        x = x + y
    if "cross" in p and mem is not None:
        tc = {} if taps is not None else None
        h = apply_norm(p["ln_cross"], x, cfg)
        yc = attn_mod.apply_cross_attn(p["cross"], h, mem, cfg, taps=tc)
        if t is not None:
            for kname, vv in tc.items():
                t["cross_" + kname] = vv
        x = x + yc
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg)
        if kind == "rwkv":
            y, _ = ssm_mod.apply_rwkv_channel(p["mlp"], h, cfg, taps=t)
        elif is_moe:
            y, aux = mlp_mod.apply_moe(p["mlp"], h, cfg, taps=t,
                                       train=train)
        else:
            y = mlp_mod.apply_mlp(p["mlp"], h, cfg, taps=t)
        x = x + y
    if taps is not None:
        merge_taps(taps, t, "")
    return x, aux


def init_block_cache(cfg, kind: str, batch: int, max_len: int):
    if kind in ("attn", "swa"):
        return attn_mod.init_cache(cfg, kind, batch, max_len)
    if kind == "mamba":
        return ssm_mod.init_mamba_state(cfg, batch)
    if kind == "rwkv":
        return ssm_mod.init_rwkv_state(cfg, batch)
    raise ValueError(kind)


def decode_block(p, x, cache, cfg, kind: str, is_moe: bool, *,
                 cross_cache=None):
    """One-token decode. x: (B,1,D). Returns (x, new_cache)."""
    with jax.named_scope(_mixer_scope(kind)):
        h = apply_norm(p["ln1"], x, cfg)
        if kind in ("attn", "swa"):
            y, new_cache = attn_mod.decode_attn(p["mixer"], h, cache, cfg,
                                                kind)
        elif kind == "mamba":
            y, ms = ssm_mod.apply_mamba(p["mixer"], h, cfg, state=cache)
            new_cache = ms
        else:  # rwkv
            y, ts = ssm_mod.apply_rwkv_time(p["mixer"], h, cfg,
                                            state=cache["time"])
            new_cache = dict(cache, time=ts)
        x = x + y
    if "cross" in p and cross_cache is not None:
        h = apply_norm(p["ln_cross"], x, cfg)
        x = x + attn_mod.decode_cross_attn(p["cross"], h, cross_cache, cfg)
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg)
        if kind == "rwkv":
            y, cs = ssm_mod.apply_rwkv_channel(p["mlp"], h, cfg,
                                               state=cache["channel"])
            new_cache = dict(new_cache, channel=cs)
        elif is_moe:
            y, _ = mlp_mod.apply_moe(p["mlp"], h, cfg)
        else:
            y = mlp_mod.apply_mlp(p["mlp"], h, cfg)
        return x + y, new_cache
