"""Attention mixers: GQA (full / sliding-window), MLA (DeepSeek-V3), cross.

CORP integration
----------------
* taps: every attention layer emits post-rope per-head ``q`` (B,T,H,dq) and
  ``k`` (B,T,Hkv,dq) when taping — the bilinear logit statistics the paper's
  Alg. 4/5 need. For MLA the tap covers the *nope* block only (the rope block
  is position-structural and is never pruned, see DESIGN.md).
* pruned models: ``cfg.eff_qk < cfg.qk_full``. RoPE frequencies for the kept
  rotary pairs are stored as a per-head buffer ``rope_inv`` inside the params
  (frozen in the optimizer), because the kept pair set differs per layer/head.
* rope-aware compensation folds per-pair 2x2 rotation-scaling blocks into
  W_q/W_k (class-2 compensator); qk-norm archs fold per-pair positive scales
  into the norm scale vectors (class-3); no-rope archs use the paper's full
  SVD fold (class-1). See repro.core.solve.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distrib.sharding import constrain, constrain_qkv
from repro.kernels.flash_attention import ops as flash_ops
from repro.models.common import (apply_rope, dense_init, dtype_of,
                                 rms_head_norm, rope_freqs, tap)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attn(key, cfg, kind: str, cross: bool = False):
    """kind: 'attn' | 'swa'; cross=True for decoder cross-attention."""
    if cfg.mla is not None and not cross:
        return _init_mla(key, cfg)
    dt = dtype_of(cfg)
    D, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dq, dv = cfg.eff_qk, cfg.d_head
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (D, H, dq), dt),
        "wk": dense_init(ks[1], (D, Hkv, dq), dt),
        "wv": dense_init(ks[2], (D, Hkv, dv), dt),
        "wo": dense_init(ks[3], (H, dv, D), dt, scale=1.0 / np.sqrt(H * dv)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dq), jnp.float32)
        p["bk"] = jnp.zeros((Hkv, dq), jnp.float32)
        p["bv"] = jnp.zeros((Hkv, dv), jnp.float32)
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((dq,), jnp.float32)
        p["k_scale"] = jnp.ones((dq,), jnp.float32)
    if _uses_rope(cfg):
        theta = cfg.rope_theta_local if kind == "swa" else cfg.rope_theta
        inv = jnp.asarray(rope_freqs(dq, theta), jnp.float32)
        # per-head copy so pruning can gather kept pair frequencies per head
        p["rope_inv_q"] = jnp.tile(inv[None, :], (H, 1))
        p["rope_inv_k"] = jnp.tile(inv[None, :], (Hkv, 1))
    return p


def _uses_rope(cfg) -> bool:
    return cfg.family == "lm" and cfg.rwkv is None


def _init_mla(key, cfg):
    dt = dtype_of(cfg)
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    nope = cfg.eff_qk           # prunable block
    ks = jax.random.split(key, 8)
    inv = jnp.asarray(rope_freqs(m.qk_rope_dim, cfg.rope_theta), jnp.float32)
    return {
        "w_dq": dense_init(ks[0], (D, m.q_lora_rank), dt),
        "q_norm_scale": jnp.ones((m.q_lora_rank,), jnp.float32),
        "w_uq_nope": dense_init(ks[1], (m.q_lora_rank, H, nope), dt),
        "w_uq_rope": dense_init(ks[2], (m.q_lora_rank, H, m.qk_rope_dim), dt),
        "w_dkv": dense_init(ks[3], (D, m.kv_lora_rank), dt),
        "w_k_rope": dense_init(ks[4], (D, m.qk_rope_dim), dt),
        "kv_norm_scale": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "w_uk_nope": dense_init(ks[5], (m.kv_lora_rank, H, nope), dt),
        "w_uv": dense_init(ks[6], (m.kv_lora_rank, H, m.v_dim), dt),
        "wo": dense_init(ks[7], (H, m.v_dim, D), dt,
                         scale=1.0 / np.sqrt(H * m.v_dim)),
        "rope_inv": inv,
    }


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg, positions, kind, taps):
    """Common Q/K/V projection + bias + qk-norm + rope + tap."""
    dt = x.dtype
    q = jnp.einsum("btd,dhq->bthq", x, p["wq"])
    k = jnp.einsum("btd,dhq->bthq", x, p["wk"])
    v = jnp.einsum("btd,dhv->bthv", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if "q_scale" in p:
        q = rms_head_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_scale"], cfg.norm_eps)
    if "rope_inv_q" in p:
        q = _rope_gathered(q, positions, p["rope_inv_q"])
        k = _rope_gathered(k, positions, p["rope_inv_k"])
    q, k, v = constrain_qkv(q, k, v)
    tap(taps, "q", q)
    tap(taps, "k", k)
    return q, k, v


def _rope_gathered(x, positions, inv):
    """Rope with per-head frequency table inv: (H, D/2)."""
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv  # (B,T,H,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_attn(p, x, cfg, kind, *, positions, taps=None, return_cache=False,
               mask_kind="causal"):
    """Full-sequence attention. x: (B, T, D).

    mask_kind: 'causal' | 'window' | 'full'. Returns (y, cache|None).
    """
    if cfg.mla is not None and "w_dq" in p:
        return _apply_mla(p, x, cfg, positions=positions, taps=taps,
                          return_cache=return_cache)
    q, k, v = _project_qkv(p, x, cfg, positions, kind, taps)
    window = cfg.sliding_window if (kind == "swa" and mask_kind != "full") else None
    scale = 1.0 / np.sqrt(cfg.qk_full if cfg.qk_kept is None else cfg.qk_full)
    o = flash_ops.attention(q, k, v, causal=(mask_kind != "full"),
                            window=window, scale=scale)
    y = jnp.einsum("bthv,hvd->btd", o, p["wo"])
    cache = None
    if return_cache:
        cache = {"k": k, "v": v,
                 "pos": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
    return y, cache


def _apply_mla(p, x, cfg, *, positions, taps=None, return_cache=False):
    m = cfg.mla
    B, T, D = x.shape
    cq = jnp.einsum("btd,dr->btr", x, p["w_dq"])
    cq = rms_head_norm(cq, p["q_norm_scale"], cfg.norm_eps)
    q_nope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_nope"])
    q_rope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_rope"])
    ckv = jnp.einsum("btd,dr->btr", x, p["w_dkv"])
    k_rope = jnp.einsum("btd,dq->btq", x, p["w_k_rope"])
    ckv_n = rms_head_norm(ckv, p["kv_norm_scale"], cfg.norm_eps)
    k_nope = jnp.einsum("btr,rhq->bthq", ckv_n, p["w_uk_nope"])
    v = jnp.einsum("btr,rhv->bthv", ckv_n, p["w_uv"])
    # rope on the decoupled block (shared key, per-head query)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope1 = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    tap(taps, "q", q_nope)
    tap(taps, "k", k_nope)
    k_rope_h = jnp.broadcast_to(k_rope1, (B, T, cfg.n_heads, m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    q_full, k_full, v = constrain_qkv(q_full, k_full, v)
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    o = flash_ops.attention(q_full, k_full, v, causal=True, scale=scale)
    y = jnp.einsum("bthv,hvd->btd", o, p["wo"])
    cache = None
    if return_cache:
        cache = {"ckv": ckv_n, "k_rope": k_rope1[:, :, 0, :],
                 "pos": jnp.full((B,), T, jnp.int32)}
    return y, cache


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------

def apply_cross_attn(p, x, mem, cfg, *, taps=None):
    """x: (B, T, D) decoder states; mem: (B, S, D) encoder memory."""
    dt = x.dtype
    q = jnp.einsum("btd,dhq->bthq", x, p["wq"])
    k = jnp.einsum("bsd,dhq->bshq", mem, p["wk"])
    v = jnp.einsum("bsd,dhv->bshv", mem, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    tap(taps, "q", q)
    tap(taps, "k", k)
    scale = 1.0 / np.sqrt(cfg.qk_full)
    o = flash_ops.attention(q, k, v, causal=False, scale=scale)
    return jnp.einsum("bthv,hvd->btd", o, p["wo"])


def precompute_cross_cache(p, mem, cfg):
    k = jnp.einsum("bsd,dhq->bshq", mem, p["wk"])
    v = jnp.einsum("bsd,dhv->bshv", mem, p["wv"])
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return {"k_mem": k, "v_mem": v}


# ---------------------------------------------------------------------------
# decode (one new token against a KV cache)
# ---------------------------------------------------------------------------

def init_cache(cfg, kind: str, batch: int, max_len: int):
    """Allocate an empty KV cache for one attention layer."""
    dt = dtype_of(cfg)
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    S = min(max_len, cfg.sliding_window) if kind == "swa" else max_len
    dq, dv = cfg.eff_qk, cfg.d_head
    c = {
        "k": jnp.zeros((batch, S, cfg.n_kv_heads, dq), dt),
        "v": jnp.zeros((batch, S, cfg.n_kv_heads, dv), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if kind == "swa":
        c["abs_pos"] = jnp.full((batch, S), -1, jnp.int32)
    return c


def decode_attn(p, x, cache, cfg, kind):
    """x: (B, 1, D) one new token. Returns (y, new_cache)."""
    if cfg.mla is not None and "w_dq" in p:
        return _decode_mla(p, x, cache, cfg)
    B = x.shape[0]
    pos = cache["pos"]                          # (B,) current length
    positions = pos[:, None]                    # (B, 1)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, kind, None)
    S = cache["k"].shape[1]
    slot = jnp.mod(pos, S) if kind == "swa" else pos
    with jax.named_scope("kv_write"):
        k = _scatter_time(cache["k"], k_new[:, 0], slot)
        v = _scatter_time(cache["v"], v_new[:, 0], slot)
    if kind == "swa":
        abs_pos = _scatter_time(cache["abs_pos"][..., None],
                                pos[:, None], slot)[..., 0]
        valid = (abs_pos >= 0) & (abs_pos >= (pos[:, None] - S + 1))
    else:
        key_idx = jnp.arange(S)[None, :]
        valid = key_idx <= pos[:, None]
    scale = 1.0 / np.sqrt(cfg.qk_full)
    y = _decode_sdpa(q, k, v, valid, scale, cfg)
    o = jnp.einsum("bhv,hvd->bd", y, p["wo"])[:, None, :]
    new_cache = dict(cache, k=k, v=v, pos=pos + 1)
    if kind == "swa":
        new_cache["abs_pos"] = abs_pos
    return o, new_cache


def _scatter_time(buf, val, slot):
    """buf: (B, S, ...), val: (B, ...), slot: (B,) — write val at [b, slot[b]].

    Indexed scatter (not a one-hot rewrite): XLA updates in place, so the
    decode step never re-materializes the cache (§Perf iteration G1)."""
    B = buf.shape[0]
    return buf.at[jnp.arange(B), slot].set(val.astype(buf.dtype))


def _decode_sdpa(q, k, v, valid, scale, cfg):
    """q: (B,1,H,dq); k/v: (B,S,Hkv,d); valid: (B,S) -> (B,H,dv).

    Dispatches to the split-KV flash-decoding Pallas kernel on TPU
    (repro.kernels.flash_decode); the jnp path contracts the cache in its
    storage dtype with fp32 accumulation (preferred_element_type) — a
    wholesale .astype(f32) would materialize an fp32 copy of the entire KV
    cache per step (§Perf iteration G1).
    """
    import os
    if jax.default_backend() == "tpu" or os.environ.get("REPRO_DECODE_IMPL"):
        from repro.kernels.flash_decode import ops as fd_ops
        return fd_ops.decode_attention(q[:, 0], k, v, valid, scale=scale)
    from repro.kernels import note_impl
    note_impl("flash_decode", "xla")
    B, _, H, dq = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q[:, 0].reshape(B, Hkv, g, dq)
    logits = jnp.einsum("bngq,bsnq->bngs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bngs,bsnv->bngv", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, -1).astype(q.dtype)


def _decode_mla(p, x, cache, cfg):
    """MLA decode with the absorbed-matmul trick (latent-space cache)."""
    m = cfg.mla
    B = x.shape[0]
    pos = cache["pos"]
    positions = pos[:, None]
    cq = jnp.einsum("btd,dr->btr", x, p["w_dq"])
    cq = rms_head_norm(cq, p["q_norm_scale"], cfg.norm_eps)
    q_nope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_nope"])[:, 0]
    q_rope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_rope"])
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)[:, 0]
    ckv_new = jnp.einsum("btd,dr->btr", x, p["w_dkv"])
    ckv_new = rms_head_norm(ckv_new, p["kv_norm_scale"], cfg.norm_eps)
    kr_new = jnp.einsum("btd,dq->btq", x, p["w_k_rope"])
    kr_new = apply_rope(kr_new[:, :, None, :], positions,
                        cfg.rope_theta)[:, 0, 0]
    ckv = _scatter_time(cache["ckv"], ckv_new[:, 0], pos)
    krope = _scatter_time(cache["k_rope"], kr_new, pos)
    # absorb W_uk into q: q_eff (B,H,r)
    q_eff = jnp.einsum("bhq,rhq->bhr", q_nope, p["w_uk_nope"])
    S = ckv.shape[1]
    lo_n = jnp.einsum("bhr,bsr->bhs", q_eff.astype(jnp.float32),
                      ckv.astype(jnp.float32))
    lo_r = jnp.einsum("bhq,bsq->bhs", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32))
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    logits = (lo_n + lo_r) * scale
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, ckv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), p["w_uv"])
    y = jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None, :]
    return y, dict(cache, ckv=ckv, k_rope=krope, pos=pos + 1)


def decode_cross_attn(p, x, cross_cache, cfg):
    """Decoder cross-attention during decode: memory K/V precomputed."""
    q = jnp.einsum("btd,dhq->bthq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    k, v = cross_cache["k_mem"], cross_cache["v_mem"]
    S = k.shape[1]
    valid = jnp.ones((x.shape[0], S), bool)
    y = _decode_sdpa(q, k, v, valid, 1.0 / np.sqrt(cfg.qk_full), cfg)
    return jnp.einsum("bhv,hvd->bd", y, p["wo"])[:, None, :]
