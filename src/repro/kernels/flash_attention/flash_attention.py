"""Pallas TPU flash attention (forward) with explicit BlockSpec VMEM tiling.

Blockwise online-softmax attention over (q_block, kv_block) tiles:
  grid = (batch, q_heads, num_q_blocks, num_kv_blocks)  [kv innermost]
  VMEM scratch carries the running (max, denom, accumulator) across the kv
  grid dimension; the output tile is written once on the last kv block.

GQA is handled by the k/v index maps (query head h reads kv head h // g).
Any T and S: the wrapper pads both up to a whole number of blocks (at most
``bq``/``bk`` rows, multiples of 8) and the kernel masks the padded keys, so
no block ever spans a whole unaligned sequence (which overflows VMEM at long
lengths). On CPU it runs under ``interpret=True`` for validation against
``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, bq, bk, t, s, nk):
    """t, s: the unpadded query/key lengths (queries right-aligned)."""
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, dq)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, dq)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, dv)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    # absolute positions (right-aligned queries for q_len < kv_len)
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (s - t)
    ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = ki < s                                 # padded keys never count
    if causal:
        mask = mask & (ki <= qi)
    if window is not None:
        mask = mask & (ki > qi - window)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]                           # (bq, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)   # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _block(n: int, cap: int) -> int:
    """Block rows for a length-``n`` axis: ``cap`` once ``n`` exceeds it,
    else ``n`` rounded up to the 8-row sublane tile (one whole block)."""
    return cap if n > cap else -(-n // 8) * 8


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    bq=128, bk=128, interpret=False):
    """q: (B,T,H,dq), k: (B,S,Hkv,dq), v: (B,S,Hkv,dv) -> (B,T,H,dv)."""
    B, T, H, dq = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = H // Hkv
    if scale is None:
        scale = float(1.0 / np.sqrt(dq))
    bq, bk = _block(T, bq), _block(S, bk)
    Tp, Sp = -(-T // bq) * bq, -(-S // bk) * bk
    nq, nk = Tp // bq, Sp // bk

    # (B, H, T, dq) layout for contiguous head tiles, padded to whole blocks
    def tiles(x, n):
        x = x.transpose(0, 2, 1, 3)
        return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))
    qt, kt, vt = tiles(q, Tp), tiles(k, Sp), tiles(v, Sp)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, t=T, s=S, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dq), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dq), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :T].transpose(0, 2, 1, 3)
