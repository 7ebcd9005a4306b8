"""Public attention op with backend dispatch.

impl resolution (env ``REPRO_ATTN_IMPL`` overrides):
  * 'pallas'  : Pallas TPU kernel (forward) — selected on TPU backends. It
                runs one of two block shapes, picked from the call's
                masking and lengths (``whole_sequence_block``):
                whole-sequence for bidirectional, unwindowed attention
                whose sequences fit VMEM whole (ViT, short
                cross-attention: one program per block of sequences, all
                heads, q/k/v read in their projected layout), tiled
                online softmax for everything else (causal or windowed
                prefill, long sequences). Each call records its path as
                ``attention_blocks:whole_sequence`` or ``:tiled`` in
                ``kernels.RESOLVED``.
  * 'xla'     : memory-sane chunked online-softmax attention in pure jnp
                (lax.scan over q- and kv-chunks) — selected on CPU/GPU and
                used for all dry-run lowering. Never materializes the full
                (T, S) logit matrix.
  * 'ref'     : small-shape oracle (full logits) — picked automatically for
                tiny inputs where chunking is pointless.
  * 'interpret': Pallas kernel under interpret=True (kernel tests).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import note_impl, per_device
from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.flash_attention import (
    flash_attention, whole_sequence_block)

_SMALL = 1 << 20  # T*S below this: just use the oracle


def _resolve_impl(T: int, S: int, bq: int, bk: int) -> str:
    impl = os.environ.get("REPRO_ATTN_IMPL", "")
    if impl:
        return impl
    if jax.default_backend() == "tpu":
        return "pallas"
    if T * S <= _SMALL or T % min(bq, T) or S % min(bk, S):
        return "ref"
    return "xla"


def attention(q, k, v, *, causal=True, window=None, scale=None,
              impl=None, bq=512, bk=1024):
    """q: (B,T,H,dq), k: (B,S,Hkv,dq), v: (B,S,Hkv,dv) -> (B,T,H,dv)."""
    B, T, H, dq = q.shape
    S = k.shape[1]
    if scale is None:
        scale = float(1.0 / np.sqrt(dq))
    impl = note_impl("flash_attention", impl or _resolve_impl(T, S, bq, bk))
    if impl == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window,
                              scale=scale)
    if impl in ("pallas", "interpret"):
        def kern(q, k, v):
            # the kernel picks its path from the shapes one device holds
            whole = whole_sequence_block(q.shape, k.shape, v.shape, q.dtype,
                                         causal=causal, window=window)
            note_impl("attention_blocks",
                      "whole_sequence" if whole else "tiled")
            return flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale,
                                   interpret=(impl == "interpret"))

        heads = ("batch", None, "model", None)
        return per_device(kern, (q, k, v), (heads,) * 3, heads)
    return _chunked(q, k, v, causal=causal, window=window, scale=scale,
                    bq=min(bq, T), bk=min(bk, S))


# ---------------------------------------------------------------------------
# chunked XLA implementation (online softmax over kv chunks)
# ---------------------------------------------------------------------------

def _chunked(q, k, v, *, causal, window, scale, bq, bk):
    B, T, H, dq = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = H // Hkv
    nq, nk = T // bq, S // bk

    # chunk-major layouts for scan
    qc = q.reshape(B, nq, bq, Hkv, g, dq).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(B, nk, bk, Hkv, dq).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, nk, bk, Hkv, dv).transpose(1, 0, 3, 2, 4)
    off = S - T  # right-aligned queries

    def q_step(_, qi_i):
        qi, i = qi_i            # (B,Hkv,g,bq,dq), scalar chunk index
        m0 = jnp.full((B, Hkv, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, bq, dv), jnp.float32)

        def kv_step(carry, kv_j):
            m, l, acc = carry
            kj, vj, j = kv_j

            def skip(operand):
                return operand[0], operand[1], operand[2]

            def compute(operand):
                m, l, acc = operand
                logits = jnp.einsum(
                    "bngqd,bnkd->bngqk", qi.astype(jnp.float32),
                    kj.astype(jnp.float32)) * scale
                qpos = (i * bq + jnp.arange(bq) + off)[:, None]
                kpos = (j * bk + jnp.arange(bk))[None, :]
                mask = jnp.ones((bq, bk), bool)
                if causal:
                    mask = mask & (kpos <= qpos)
                if window is not None:
                    mask = mask & (kpos > qpos - window)
                logits = jnp.where(mask, logits, -1e30)
                mc = jnp.max(logits, axis=-1)
                mn = jnp.maximum(m, mc)
                p = jnp.exp(logits - mn[..., None])
                corr = jnp.exp(m - mn)
                ln = l * corr + jnp.sum(p, axis=-1)
                an = acc * corr[..., None] + jnp.einsum(
                    "bngqk,bnkv->bngqv", p, vj.astype(jnp.float32))
                return mn, ln, an

            # block-skip: chunk entirely above the diagonal / outside window
            needed = jnp.array(True)
            if causal:
                needed = needed & (j * bk <= i * bq + off + bq - 1)
            if window is not None:
                needed = needed & ((j + 1) * bk - 1 > i * bq + off - window)
            m, l, acc = jax.lax.cond(needed, compute, skip, (m, l, acc))
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kc, vc, jnp.arange(nk)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o.astype(q.dtype)

    _, oc = jax.lax.scan(q_step, None, (qc, jnp.arange(nq)))
    # (nq, B, Hkv, g, bq, dv) -> (B, T, H, dv)
    return oc.transpose(1, 0, 4, 2, 3, 5).reshape(B, T, H, dv)
