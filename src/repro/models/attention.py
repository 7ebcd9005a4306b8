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
                                 rms_head_norm, rope_freqs, tap, yarn_gains,
                                 yarn_inv_freq)


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
    }


def mla_rope(cfg):
    """(inverse frequencies, cos/sin gain) of MLA's decoupled rope dims:
    plain rope at ``rope_theta``, or YaRN under ``cfg.rope_scaling``."""
    r, rs = cfg.mla.qk_rope_dim, cfg.rope_scaling
    if rs is None:
        return rope_freqs(r, cfg.rope_theta), 1.0
    return yarn_inv_freq(r, cfg.rope_theta, rs), yarn_gains(rs)[0]


def latent_lanes(cfg) -> int:
    """Lanes of one latent cache row: [ckv | k_rope] zero-padded to whole
    128-lane tiles (576 -> 640 for DeepSeek-V3, which a TPU tile pads to in
    memory anyway). An unaligned row lets the compiler lay the cache out
    time-minor and copy it whole on every step."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_dim) // 128) * 128


def _latent_row(ckv, k_rope, lanes):
    """(..., R), (..., r) -> the cache row (..., lanes)."""
    pad = lanes - ckv.shape[-1] - k_rope.shape[-1]
    zeros = jnp.zeros(ckv.shape[:-1] + (pad,), ckv.dtype)
    return jnp.concatenate([ckv, k_rope.astype(ckv.dtype), zeros], -1)


def mla_scale(cfg) -> float:
    """Softmax scale of MLA: 1/sqrt(nope + rope) of the published widths
    (CORP folds its compensation into the weights and keeps the scale),
    times YaRN's mscale^2."""
    m = cfg.mla
    soft = 1.0 if cfg.rope_scaling is None else yarn_gains(
        cfg.rope_scaling)[1]
    return float(soft / np.sqrt(m.qk_nope_dim + m.qk_rope_dim))


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg, positions, kind, taps, folded=False):
    """Common Q/K/V projection + bias + qk-norm + rope + tap.

    ``folded``: project each of q/k/v as one (D, H*d) matmul
    (``project_folded``), for bidirectional attention; causal callers keep
    the per-head einsums."""
    dt = x.dtype
    if folded:
        q, k, v = (project_folded(x, p[w], p.get(b))
                   for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    else:
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


def project_folded(x, w, b=None):
    """x (B, T, D) @ w (D, H, d) [+ b (H, d)] -> (B, T, H, d) as one
    (D, H*d) matmul: the heads leave it folded into lanes, the
    ``(B, T, H*d)`` layout the whole-sequence attention kernel reads, so
    the reshapes on either side of the kernel stay bitcasts."""
    y = jnp.einsum("btd,dk->btk", x, w.reshape(w.shape[0], -1))
    if b is not None:
        y = y + b.reshape(-1).astype(y.dtype)
    return y.reshape(y.shape[:2] + w.shape[1:])


def output_folded(o, wo):
    """o (B, T, H, dv) @ wo (H, dv, D) -> (B, T, D), read with the heads
    folded into lanes as the whole-sequence kernel writes them."""
    return jnp.einsum("btk,kd->btd", o.reshape(o.shape[:2] + (-1,)),
                      wo.reshape(-1, wo.shape[-1]))


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
    full = mask_kind == "full"
    q, k, v = _project_qkv(p, x, cfg, positions, kind, taps, folded=full)
    window = cfg.sliding_window if (kind == "swa" and not full) else None
    scale = 1.0 / np.sqrt(cfg.qk_full if cfg.qk_kept is None else cfg.qk_full)
    o = flash_ops.attention(q, k, v, causal=not full, window=window,
                            scale=scale)
    y = (output_folded(o, p["wo"]) if full
         else jnp.einsum("bthv,hvd->btd", o, p["wo"]))
    cache = None
    if return_cache:
        cache = {"k": fold_heads(k), "v": fold_heads(v),
                 "pos": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
    return y, cache


def fold_heads(x):
    """(B, T, H, d) -> (B, T, H*d): the cache layout, heads folded into
    lanes (``init_cache``)."""
    return x.reshape(x.shape[:2] + (-1,))


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
    inv, gain = mla_rope(cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, inv, gain)
    k_rope1 = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                         inv, gain)
    tap(taps, "q", q_nope)
    tap(taps, "k", k_nope)
    k_rope_h = jnp.broadcast_to(k_rope1, (B, T, cfg.n_heads, m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    q_full, k_full, v = constrain_qkv(q_full, k_full, v)
    o = flash_ops.attention(q_full, k_full, v, causal=True,
                            scale=mla_scale(cfg))
    y = jnp.einsum("bthv,hvd->btd", o, p["wo"])
    cache = None
    if return_cache:
        cache = {"lat": _latent_row(ckv_n, k_rope1[:, :, 0, :],
                                    latent_lanes(cfg)),
                 "pos": jnp.full((B,), T, jnp.int32)}
    return y, cache


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------

def apply_cross_attn(p, x, mem, cfg, *, taps=None):
    """x: (B, T, D) decoder states; mem: (B, S, D) encoder memory."""
    q = project_folded(x, p["wq"], p.get("bq"))
    k = project_folded(mem, p["wk"], p.get("bk"))
    v = project_folded(mem, p["wv"], p.get("bv"))
    tap(taps, "q", q)
    tap(taps, "k", k)
    scale = 1.0 / np.sqrt(cfg.qk_full)
    o = flash_ops.attention(q, k, v, causal=False, scale=scale)
    return output_folded(o, p["wo"])


def precompute_cross_cache(p, mem, cfg):
    k = jnp.einsum("bsd,dhq->bshq", mem, p["wk"])
    v = jnp.einsum("bsd,dhv->bshv", mem, p["wv"])
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return {"k_mem": fold_heads(k), "v_mem": fold_heads(v)}


# ---------------------------------------------------------------------------
# decode (one new token against a KV cache)
# ---------------------------------------------------------------------------

def init_cache(cfg, kind: str, batch: int, max_len: int):
    """Allocate an empty KV cache for one attention layer.

    K and V rows are stored ``(B, S, Hkv*d)``: time where the slot, prefix
    and window code index it, kv heads folded into lanes, which is the
    layout ``flash_decode`` reads in place."""
    dt = dtype_of(cfg)
    if cfg.mla is not None:
        # one latent row per token: [normed ckv | roped k | zeros], read
        # whole as the key and its first kv_lora_rank lanes as the value
        return {
            "lat": jnp.zeros((batch, max_len, latent_lanes(cfg)), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    S = min(max_len, cfg.sliding_window) if kind == "swa" else max_len
    dq, dv = cfg.eff_qk, cfg.d_head
    c = {
        "k": jnp.zeros((batch, S, cfg.n_kv_heads * dq), dt),
        "v": jnp.zeros((batch, S, cfg.n_kv_heads * dv), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if kind == "swa":
        c["abs_pos"] = jnp.full((batch, S), -1, jnp.int32)
    return c


# cache leaves decode_attn reads and writes stacked
IN_PLACE = ("k", "v", "lat")


def layer_of(tree, layer):
    """Leaves of a stacked cache tree at ``layer`` (a slice)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        tree)


def set_layer(tree, layer, one):
    """Write one layer's leaves back into the stacked tree at ``layer``."""
    return jax.tree.map(
        lambda a, o: jax.lax.dynamic_update_index_in_dim(
            a, o.astype(a.dtype), layer, 0), tree, one)


def decode_attn(p, x, cache, cfg, kind, layer=None):
    """x: (B, 1, D) one new token. Returns (y, new_cache).

    ``cache`` is one layer's, or with ``layer`` (int32 scalar) the stack
    of a scanned segment, every leaf with a leading layer axis. K/V rows
    are then written at ``[layer, b, pos[b]]`` and ``flash_decode`` reads
    the stack in place; the small leaves (``pos``, ``abs_pos``) are read
    and written back at ``layer``. One layer's own cache is a stack of one.
    MLA's latent rows are written and read the same way.
    """
    if layer is None:
        y, stack = decode_attn(p, x, jax.tree.map(lambda a: a[None], cache),
                               cfg, kind, layer=jnp.int32(0))
        return y, jax.tree.map(lambda a: a[0], stack)
    if cfg.mla is not None and "w_dq" in p:
        return _decode_mla(p, x, cache, cfg, layer)
    small = layer_of({n: a for n, a in cache.items() if n not in IN_PLACE},
                     layer)
    B = x.shape[0]
    pos = small["pos"]                          # (B,) current length
    positions = pos[:, None]                    # (B, 1)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, kind, None)
    S = cache["k"].shape[2]
    slot = jnp.mod(pos, S) if kind == "swa" else pos
    with jax.named_scope("kv_write"):
        k = _scatter_time(cache["k"], k_new[:, 0].reshape(B, -1), slot,
                          layer)
        v = _scatter_time(cache["v"], v_new[:, 0].reshape(B, -1), slot,
                          layer)
    small["pos"] = pos + 1
    if kind == "swa":
        abs_pos = _scatter_time(small["abs_pos"], pos, slot)
        valid = (abs_pos >= 0) & (abs_pos >= (pos[:, None] - S + 1))
        small["abs_pos"] = abs_pos
    else:
        key_idx = jnp.arange(S)[None, :]
        valid = key_idx <= pos[:, None]
    scale = 1.0 / np.sqrt(cfg.qk_full)
    y = _decode_sdpa(q, k, v, layer, valid, scale)
    o = jnp.einsum("bhv,hvd->bd", y, p["wo"])[:, None, :]
    new_cache = set_layer({n: cache[n] for n in small}, layer, small)
    return o, dict(new_cache, k=k, v=v)


def _scatter_time(buf, val, slot, layer=None):
    """buf: (B, S, ...), val: (B, ...), slot: (B,) — write val at [b, slot[b]];
    with ``layer``, buf is a stack (L, B, S, ...) written at [layer, b, slot[b]].

    Indexed scatter (not a one-hot rewrite): XLA updates in place, so the
    decode step never re-materializes the cache (§Perf iteration G1)."""
    rows = (jnp.arange(val.shape[0]), slot)
    idx = rows if layer is None else (layer,) + rows
    return buf.at[idx].set(val.astype(buf.dtype))


def _decode_sdpa(q, k, v, layer, valid, scale):
    """q: (B,1,H,dq); k/v: stacked (L,B,S,Hkv*d) read at ``layer``;
    valid: (B,S) -> (B,H,dv).

    Dispatches to the split-KV flash-decoding Pallas kernel on TPU
    (repro.kernels.flash_decode), which reads the stack in place; the jnp
    path slices the layer and contracts it in its storage dtype with fp32
    accumulation (preferred_element_type) — a wholesale .astype(f32) would
    materialize an fp32 copy of the entire KV cache per step (§Perf
    iteration G1).
    """
    import os
    from repro.kernels.flash_decode import ops as fd_ops
    if jax.default_backend() == "tpu" or os.environ.get("REPRO_DECODE_IMPL"):
        return fd_ops.decode_attention(q[:, 0], k, v, valid, layer=layer,
                                       scale=scale)
    from repro.kernels import note_impl
    note_impl("flash_decode", "xla")
    note_impl("decode_cache", "sliced", n=k.shape[0])
    B, _, H, dq = q.shape
    Hkv = k.shape[-1] // dq
    k, v = fd_ops.layer_rows(k, layer, Hkv), fd_ops.layer_rows(v, layer, Hkv)
    g = H // Hkv
    qg = q[:, 0].reshape(B, Hkv, g, dq)
    logits = jnp.einsum("bngq,bsnq->bngs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bngs,bsnv->bngv", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, -1).astype(q.dtype)


def _decode_mla(p, x, cache, cfg, layer):
    """MLA decode, absorbed: q_nope·W_uk turns each head's query into the
    latent space, so attention runs over the stacked latent rows
    ``(L, B, S, latent_lanes)`` where they lie (``flash_decode``'s latent
    kernel), and W_uv lifts the latent output to the heads."""
    m = cfg.mla
    R = m.kv_lora_rank
    with jax.named_scope("mla"):
        pos = layer_of({"pos": cache["pos"]}, layer)["pos"]  # (B,)
        positions = pos[:, None]
        inv, gain = mla_rope(cfg)
        cq = jnp.einsum("btd,dr->btr", x, p["w_dq"])
        cq = rms_head_norm(cq, p["q_norm_scale"], cfg.norm_eps)
        q_nope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_nope"])[:, 0]
        q_rope = jnp.einsum("btr,rhq->bthq", cq, p["w_uq_rope"])
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta, inv,
                            gain)[:, 0]
        ckv_new = jnp.einsum("btd,dr->btr", x, p["w_dkv"])
        ckv_new = rms_head_norm(ckv_new, p["kv_norm_scale"], cfg.norm_eps)
        kr_new = jnp.einsum("btd,dq->btq", x, p["w_k_rope"])
        kr_new = apply_rope(kr_new[:, :, None, :], positions, cfg.rope_theta,
                            inv, gain)[:, 0, 0]
        lanes = cache["lat"].shape[-1]
        with jax.named_scope("kv_write"):
            lat = _scatter_time(cache["lat"], _latent_row(
                ckv_new[:, 0], kr_new, lanes), pos, layer)
        q_eff = jnp.einsum("bhq,rhq->bhr", q_nope, p["w_uk_nope"])
        q_cat = _latent_row(q_eff, q_rope, lanes)   # zeros meet the pad
        from repro.kernels.flash_decode import ops as fd_ops
        o_lat = fd_ops.decode_latent(q_cat.astype(lat.dtype), lat, pos,
                                     layer=layer, scale=mla_scale(cfg), dv=R)
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), p["w_uv"])
        y = jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None, :]
    new = set_layer({"pos": cache["pos"]}, layer, {"pos": pos + 1})
    return y, dict(new, lat=lat)


def decode_cross_attn(p, x, cross_cache, cfg, layer=None):
    """Decoder cross-attention during decode: memory K/V precomputed,
    ``(B, M, Hkv*d)``, or with ``layer`` a decoder stack read at it."""
    q = jnp.einsum("btd,dhq->bthq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    k, v = cross_cache["k_mem"], cross_cache["v_mem"]
    if layer is None:
        k, v, layer = k[None], v[None], jnp.int32(0)
    valid = jnp.ones((x.shape[0], k.shape[2]), bool)
    y = _decode_sdpa(q, k, v, layer, valid, 1.0 / np.sqrt(cfg.qk_full))
    return jnp.einsum("bhv,hvd->bd", y, p["wo"])[:, None, :]
