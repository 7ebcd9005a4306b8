"""Pallas TPU flash-decoding kernel: split-KV single-token attention.

Decode attention is memory-bound (every step streams the whole KV cache),
so the kernel's job is to read each cache block from HBM exactly once at
full bandwidth while parallelizing over the sequence axis (one q token
gives no q-parallelism — FlashDecoding's split-K trick):

  phase 1 (this kernel): grid (B, S/bs) — each program reduces one block
    of one lane's cache rows to a partial (acc, m, l) triple for all H
    query heads, written per split.
  phase 2 (tiny jnp epilogue in ops.py): logsumexp-merge the S/bs partials.

The kernel reads the cache where it is stored. K and V are stacks
``(L, B, S, Hkv*d)`` — every layer of a scanned segment, kv heads folded
into lanes — and the layer index arrives as a scalar-prefetch operand that
the block index maps read, so no slice, transpose or copy of the cache is
made before the call. Folded heads keep the blocks lane-dense (Hkv*dq
lanes of K, Hkv*dv of V) where a per-head ``(S, d)`` tile with d = 64
would pad every row to 128 lanes.

Each program serves every head of its lane: q arrives block-diagonal
``(H, Hkv*dq)`` (row h holds its query in its kv head's lanes, exact zeros
elsewhere), so one matmul gives every head's logits against only its own
kv head; P·V runs once per kv head with the other groups' rows zeroed.

MLA's absorbed decode has its own kernel, ``decode_latent_attention``:
one latent row ``[ckv | k_rope]`` per token (576 lanes for DeepSeek-V3)
serves as the key of every head, and its first ``kv_lora_rank`` lanes as
the value, so each row is read from HBM once for both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(layer_ref, q_ref, k_ref, v_ref, valid_ref, acc_ref, m_ref,
                   l_ref, *, scale, g, dv):
    del layer_ref                                  # read by the index maps
    q = q_ref[...]                                 # (H, Hkv*dq) block-diag
    k = k_ref[...]                                 # (bs, Hkv*dq)
    if q.dtype != k.dtype:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    valid = valid_ref[...] != 0                    # (1, bs)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (H, bs)
    logits = jnp.where(valid, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)      # (H, 1)
    p = jnp.exp(logits - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    head = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0) // g
    acc = jnp.zeros((p.shape[0], dv), jnp.float32)
    for n in range(v_ref.shape[-1] // dv):         # kv heads, static
        vn = v_ref[:, pl.ds(n * dv, dv)].astype(jnp.float32)  # (bs, dv)
        acc += jax.lax.dot_general(jnp.where(head == n, p, 0.0), vn,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    acc_ref[...] = acc                             # (H, dv)
    m_ref[...] = m
    l_ref[...] = l


@functools.partial(jax.jit, static_argnames=("scale", "bs", "interpret"))
def decode_attention_splits(q, k, v, valid, layer, *, scale, bs=512,
                            interpret=False):
    """q: (B,H,dq); k: (L,B,S,Hkv*dq), v: (L,B,S,Hkv*dv) — a stacked cache
    read in place at ``layer`` (int32 scalar); valid: (B,S) int/bool.

    The mask travels as (B, 1, S) int32 so its (1, bs) block obeys the
    TPU (8, 128) tiling rule (a (1, bs) block of a (B, S) array does not).

    Returns per-split partials (acc (B,ns,H,dv), m, l (B,ns,H,1)).
    """
    B, H, dq = q.shape
    S, Fk = k.shape[2], k.shape[3]
    Hkv = Fk // dq
    assert Hkv * dq == Fk and H % Hkv == 0, (q.shape, k.shape)
    dv = v.shape[-1] // Hkv
    g = H // Hkv
    bs = min(bs, S)
    assert S % bs == 0
    ns = S // bs

    eye = jnp.eye(Hkv, dtype=bool)[None, :, None, :, None]
    qbd = jnp.where(eye, q.reshape(B, Hkv, g, 1, dq), 0).reshape(B, H, Fk)
    kernel = functools.partial(_decode_kernel, scale=scale, g=g, dv=dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((None, H, Fk), lambda b, s, lyr: (b, 0, 0)),
            pl.BlockSpec((None, None, bs, Fk),
                         lambda b, s, lyr: (lyr[0], b, s, 0)),
            pl.BlockSpec((None, None, bs, v.shape[-1]),
                         lambda b, s, lyr: (lyr[0], b, s, 0)),
            pl.BlockSpec((None, 1, bs), lambda b, s, lyr: (b, 0, s)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, H, dv), lambda b, s, lyr: (b, s, 0, 0)),
            pl.BlockSpec((None, None, H, 1), lambda b, s, lyr: (b, s, 0, 0)),
            pl.BlockSpec((None, None, H, 1), lambda b, s, lyr: (b, s, 0, 0)),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, ns, H, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), qbd, k, v,
      valid.astype(jnp.int32)[:, None, :])
    return acc, m, l


# ---------------------------------------------------------------------------
# the latent shape: MLA's absorbed decode
# ---------------------------------------------------------------------------

def _latent_kernel(layer_ref, last_ref, q_ref, c_ref, o_ref, m_sc, l_sc,
                   acc_sc, *, scale, dv, bs):
    del layer_ref                                  # read by the index maps
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    q = q_ref[...]                                 # (H, C)
    c = c_ref[...]                                 # (bs, C) = [ckv | k_rope]
    logits = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (H, bs)
    row = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = row <= last_ref[b]
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
        p.astype(c.dtype), c[:, :dv], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (H, dv)
    m_sc[...] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "dv", "bs",
                                             "interpret"))
def decode_latent_attention(q, lat, last, layer, *, scale, dv, bs=1024,
                            interpret=False):
    """q: (B,H,C) absorbed queries; lat: (L,B,S,C) stacked latent rows read
    in place at ``layer`` (int32 scalar), each row read once as the key
    and its first ``dv`` lanes as the value; last: (B,) int32, the last
    valid row of each lane. Returns (B,H,dv) in q's dtype.

    MLA has one latent "head" of C lanes shared by every query head, so a
    program serves all H heads of one lane against one block of rows; the
    grid walks the blocks of a lane in order, carrying the online softmax
    in VMEM, and writes the normalised output once (no split partials).
    """
    B, H, C = q.shape
    S = lat.shape[2]
    bs = min(bs, S)
    assert S % bs == 0 and dv <= C, (q.shape, lat.shape, dv)
    kernel = functools.partial(_latent_kernel, scale=scale, dv=dv, bs=bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((None, H, C), lambda b, s, lyr, last: (b, 0, 0)),
            pl.BlockSpec((None, None, bs, C),
                         lambda b, s, lyr, last: (lyr[0], b, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, H, dv),
                               lambda b, s, lyr, last: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, dv), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), last.astype(jnp.int32), q,
      lat)
