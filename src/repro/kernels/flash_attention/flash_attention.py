"""Pallas TPU flash attention (forward), in two block shapes.

Whole-sequence path (bidirectional attention whose sequence fits VMEM, as a
ViT's 197 tokens do): grid ``(B // bb,)``, one program per block of ``bb``
sequences holding every head. q/k/v are read in their projected layout
``(B, T, H*d)``, heads folded into lanes, so no transpose or pad is made in
HBM; the full T and S are the blocks' own dims. Inside, a static loop over
the heads takes each head's logits over the whole key row, one plain
softmax (no online rescaling: S is whole), and writes its lanes of the
output.

Tiled path (everything else: causal or windowed attention, and sequences
too long for VMEM): blockwise online-softmax over (q_block, kv_block)
tiles,
  grid = (batch, q_heads, num_q_blocks, num_kv_blocks)  [kv innermost]
  VMEM scratch carries the running (max, denom, accumulator) across the kv
  grid dimension; the output tile is written once on the last kv block.
The wrapper transposes q/k/v head-major and pads T and S up to a whole
number of blocks (at most ``bq``/``bk`` rows, multiples of 8); the kernel
masks the padded keys, so no block spans a whole long sequence (which
overflows VMEM).

``whole_sequence_block`` picks between them from what a call can see: the
masking and the shapes. GQA maps query head h to kv head h // g on both
paths. On CPU both run under ``interpret=True`` for validation against
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


# VMEM the whole-sequence path may plan for: every block double-buffered
# plus one head's logits and their softmax temporaries, within the 16 MiB
# of scoped VMEM a v5e kernel gets by default.
WHOLE_VMEM_BYTES = 12 << 20


def _tile_bytes(rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, lanes) block: rows round up to the sublane
    tile (8 rows of 32 bits), lanes to 128."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * (-(-lanes // 128) * 128) * itemsize


def whole_sequence_block(q_shape, k_shape, v_shape, dtype, *, causal,
                         window) -> int:
    """Sequences per program of the whole-sequence path: the largest
    divisor of the batch whose blocks fit ``WHOLE_VMEM_BYTES``; 0 where
    the path does not apply (causal or windowed masks, or one sequence too
    long), and the tiled kernel runs."""
    if causal or window is not None:
        return 0
    B, T, H, dq = q_shape
    S, Hkv, dv = k_shape[1], k_shape[2], v_shape[-1]
    n = jnp.dtype(dtype).itemsize
    seq = 2 * (_tile_bytes(T, H * dq, n) + _tile_bytes(S, Hkv * dq, n)
               + _tile_bytes(S, Hkv * dv, n) + _tile_bytes(T, H * dv, n))
    logits = 3 * _tile_bytes(T, S, 4)
    fits = [bb for bb in range(1, B + 1)
            if B % bb == 0 and bb * seq + logits <= WHOLE_VMEM_BYTES]
    return max(fits, default=0)


def _whole_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, g, dq, dv):
    """One block of whole sequences, every head: q (bb, T, H*dq), k
    (bb, S, Hkv*dq), v (bb, S, Hkv*dv) -> o (bb, T, H*dv).

    Without GQA, and where no head straddles a 128-lane group, each head
    reads the aligned group that holds it: its q lanes kept and the other
    heads' zeroed, which adds exact zeros to the contraction (a 32-deep
    contraction costs the MXU a 128-deep pass anyway), and its output
    lanes selected into the group, which is stored once. Otherwise each
    head reads and writes its own lanes."""
    grouped = g == 1 and 128 % dq == 0 and 128 % dv == 0

    def lanes(ref, i, lo, d):
        """Lanes of ``ref[i]`` holding [lo, lo + d): (the block, its first
        lane, the mask of [lo, lo + d) in it or None where it is exact)."""
        if not grouped:
            return ref[i, :, pl.ds(lo, d)].astype(jnp.float32), lo, None
        a = lo // 128 * 128
        x = ref[i, :, a:min(a + 128, ref.shape[-1])].astype(jnp.float32)
        lane = a + jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1)
        return x, a, (lane >= lo) & (lane < lo + d)

    def sequence(i, carry):
        groups = {}                                       # first lane -> o
        for h in range(q_ref.shape[-1] // dq):            # heads, static
            n = h // g
            q, _, own = lanes(q_ref, i, h * dq, dq)
            if own is not None:
                q = jnp.where(own, q, 0.0)
            k, _, _ = lanes(k_ref, i, n * dq, dq)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (T, S)
            p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
            v, a, own = lanes(v_ref, i, n * dv, dv)
            o = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o = o / jnp.sum(p, axis=-1, keepdims=True)
            if own is None:
                o_ref[i, :, pl.ds(h * dv, dv)] = o.astype(o_ref.dtype)
            else:
                groups[a] = jnp.where(own, o, groups.get(a, 0.0))
        for a, o in groups.items():
            o_ref[i, :, a:a + o.shape[-1]] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], sequence, 0)


def _whole_sequence(q, k, v, *, scale, bb, interpret):
    """The whole-sequence path: heads fold into lanes by reshapes that
    keep the projected layout, and one program covers ``bb`` sequences."""
    B, T, H, dq = q.shape
    S, Hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    kernel = functools.partial(_whole_kernel, scale=scale, g=H // Hkv,
                               dq=dq, dv=dv)
    out = pl.pallas_call(
        kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, T, H * dq), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, S, Hkv * dq), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, S, Hkv * dv), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, T, H * dv), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="flash_attention_whole_sequence",
    )(q.reshape(B, T, H * dq), k.reshape(B, S, Hkv * dq),
      v.reshape(B, S, Hkv * dv))
    return out.reshape(B, T, H, dv)


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
    bb = whole_sequence_block(q.shape, k.shape, v.shape, q.dtype,
                              causal=causal, window=window)
    if bb:
        return _whole_sequence(q, k, v, scale=scale, bb=bb,
                               interpret=interpret)
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
