"""Pallas TPU flash-decoding kernel: split-KV single-token attention.

Decode attention is memory-bound (every step streams the whole KV cache),
so the kernel's job is to read each cache block from HBM exactly once at
full bandwidth while parallelizing over the sequence axis (one q token
gives no q-parallelism — FlashDecoding's split-K trick):

  phase 1 (this kernel): grid (B, Hkv, S/bs) — each program reduces one KV
    block to a partial (acc, m, l) triple for all g = H/Hkv query heads of
    its kv head, written per split.
  phase 2 (tiny jnp epilogue in ops.py): logsumexp-merge the S/bs partials.

VMEM per program: the (bs, d) K/V tiles + (g, dv) accumulators — the cache
never lands in VMEM twice, and splits proceed in parallel across the
sequence (unlike the fwd flash kernel's sequential kv grid walk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, acc_ref, m_ref, l_ref, *,
                   scale, g, bs):
    q = q_ref[0, 0].astype(jnp.float32)            # (g, dq)
    k = k_ref[0, 0].astype(jnp.float32)            # (bs, dq)
    v = v_ref[0, 0].astype(jnp.float32)            # (bs, dv)
    valid = valid_ref[0] != 0                      # (1, bs)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (g, bs)
    logits = jnp.where(valid, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)      # (g, 1)
    p = jnp.exp(logits - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc_ref[0, 0, 0] = acc                           # (g, dv)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


@functools.partial(jax.jit, static_argnames=("scale", "bs", "interpret"))
def decode_attention_splits(q, k, v, valid, *, scale, bs=512,
                            interpret=False):
    """q: (B,H,dq); k/v: (B,S,Hkv,d); valid: (B,S) int/bool.

    The mask travels as (B, 1, S) int32 so its (1, 1, bs) block obeys the
    TPU (8, 128) tiling rule (a (1, bs) block of a (B, S) array does not).

    Returns per-split partials (acc (B,Hkv,ns,g,dv), m, l (B,Hkv,ns,g,1)).
    """
    B, H, dq = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = H // Hkv
    bs = min(bs, S)
    assert S % bs == 0
    ns = S // bs

    qg = q.reshape(B, Hkv, g, dq)
    kt = k.transpose(0, 2, 1, 3)                     # (B,Hkv,S,dq)
    vt = v.transpose(0, 2, 1, 3)
    kernel = functools.partial(_decode_kernel, scale=scale, g=g, bs=bs)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, dq), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dq), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, dv), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, dv), lambda b, h, s: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1), lambda b, h, s: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1), lambda b, h, s: (b, h, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, ns, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, ns, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt, valid.astype(jnp.int32)[:, None, :])
    return acc, m, l
