"""Pure-jnp oracle for single-token decode attention (GQA, masked cache)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention(q, k, v, valid, scale: float):
    """q: (B,H,dq); k/v: (B,S,Hkv,d); valid: (B,S) -> (B,H,dv). fp32 math."""
    B, H, dq = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, dq).astype(jnp.float32)
    logits = jnp.einsum("bngq,bsnq->bngs", qg,
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bngs,bsnv->bngv", w, v.astype(jnp.float32))
    return o.reshape(B, H, -1).astype(q.dtype)


def decode_latent(q, lat, last, scale: float, dv: int):
    """q: (B,H,C); lat: (B,S,C) latent rows whose first ``dv`` lanes are
    the value; last: (B,) last valid row -> (B,H,dv). Contracts in the
    cache's dtype with fp32 accumulation (never an fp32 copy of the
    cache)."""
    S = lat.shape[1]
    logits = jnp.einsum("bhc,bsc->bhs", q.astype(lat.dtype), lat,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] <= last[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhs,bsr->bhr", w.astype(lat.dtype), lat[..., :dv],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)
