"""Config registry: ``get_config(arch_id)`` + reduced smoke variants."""
from __future__ import annotations

import dataclasses

from repro.configs.base import (MLAConfig, MambaConfig, ModelConfig,
                                MoEConfig, RopeScaling, RWKVConfig,
                                ShapeConfig, SHAPES)

_MODULES = {
    "granite-8b": "granite_8b",
    "gemma3-1b": "gemma3_1b",
    "deepseek-7b": "deepseek_7b",
    "qwen2-1.5b": "qwen2_1_5b",
    "internvl2-26b": "internvl2_26b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "rwkv6-3b": "rwkv6_3b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
}

ARCH_IDS = tuple(_MODULES)

DEIT_IDS = ("deit-tiny", "deit-small", "deit-base", "deit-large", "deit-huge")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id in _MODULES:
        import importlib
        mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
        return mod.CONFIG
    if arch_id in DEIT_IDS:
        from repro.configs import deit
        return getattr(deit, arch_id.upper().replace("-", "_"))
    raise KeyError(f"unknown arch id {arch_id!r}; known: {ARCH_IDS + DEIT_IDS}")


def reduced(cfg: ModelConfig, *, d_model: int = 64, layers_scale: int = 1) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests.

    Keeps the structural pattern (GQA ratio, MoE, MLA, hybrid interleave,
    enc-dec, frontends) but shrinks width/depth/experts/vocab.
    """
    period = len(cfg.pattern)
    if cfg.moe is not None:
        import math
        period = math.lcm(period, cfg.moe_every)
    n_layers = max(period, 2) * layers_scale
    if cfg.first_k_dense:
        n_layers += 1
    n_heads = max(2, min(cfg.n_heads, 4))
    n_kv = max(1, n_heads * cfg.n_kv_heads // cfg.n_heads)
    n_heads = n_kv * max(1, n_heads // n_kv)
    d_head = 16
    kw = dict(
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_head=d_head,
        d_ff=4 * d_model if cfg.moe is None else 2 * d_model,
        vocab_size=min(cfg.vocab_size, 503) if cfg.vocab_size else 0,
        sliding_window=8,
        dtype="float32",
        vocab_round=8,
    )
    if cfg.moe is not None:
        # group-limited routing keeps its shape at toy size: 8 experts in 4
        # groups, 2 groups a token, top-2
        grouped = cfg.moe.n_group > 1
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=8 if grouped else 4, top_k=2,
            d_expert=2 * d_model, num_shared=min(cfg.moe.num_shared, 1),
            n_group=4 if grouped else 1, topk_group=2 if grouped else 1,
            held=None, first_held=0)
        kw["d_ff"] = 2 * d_model
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                              qk_rope_dim=8, v_dim=16)
    if cfg.rope_scaling is not None:
        # YaRN over an original context of 64 positions: at 8 rope dims
        # every class of frequency (kept, ramped, interpolated) is present
        kw["rope_scaling"] = dataclasses.replace(
            cfg.rope_scaling, original_max_position_embeddings=64)
    if cfg.mamba is not None:
        kw["mamba"] = MambaConfig(d_state=4, d_conv=4, expand=2)
    if cfg.rwkv is not None:
        kw["rwkv"] = RWKVConfig(head_dim=16, decay_lora=8)
        kw["n_heads"] = d_model // 16
        kw["n_kv_heads"] = d_model // 16
    if cfg.first_k_dense:
        kw["first_k_dense"] = 1
        kw["dense_d_ff"] = 4 * d_model
    if cfg.n_enc_layers:
        kw["n_enc_layers"] = 2
    if cfg.family == "vit":
        kw["img_size"] = 32
        kw["patch"] = 8
        kw["n_classes"] = min(cfg.n_classes, 10) or 10
    return cfg.replace(name=cfg.name + "-reduced", **kw)


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "MambaConfig", "RopeScaling",
    "RWKVConfig",
    "ShapeConfig", "SHAPES", "ARCH_IDS", "DEIT_IDS", "get_config", "reduced",
]
