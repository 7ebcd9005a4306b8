"""Public flash-decoding op: split-KV kernel + logsumexp merge epilogue.

impl resolution (env ``REPRO_DECODE_IMPL`` overrides): 'pallas' on TPU,
'ref' elsewhere, 'interpret' for kernel tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import note_impl, per_device
from repro.kernels.flash_decode import ref as _ref
from repro.kernels.flash_decode.flash_decode import decode_attention_splits


def _resolve_impl(S: int, bs: int) -> str:
    impl = os.environ.get("REPRO_DECODE_IMPL", "")
    if impl:
        return impl
    if jax.default_backend() == "tpu" and S % bs == 0 and S >= 2 * bs:
        return "pallas"
    return "ref"


def _merge(acc, m, l):
    """Logsumexp-merge per-split partials over the split axis (ns)."""
    m_max = jnp.max(m, axis=2, keepdims=True)                # (B,Hkv,1,g,1)
    corr = jnp.exp(m - m_max)
    l_tot = jnp.sum(l * corr, axis=2)                        # (B,Hkv,g,1)
    acc_tot = jnp.sum(acc * corr, axis=2)                    # (B,Hkv,g,dv)
    return acc_tot / jnp.maximum(l_tot, 1e-30)


def decode_attention(q, k, v, valid, *, scale=None, bs=512, impl=None):
    """q: (B,H,dq); k/v: (B,S,Hkv,d); valid: (B,S) -> (B,H,dv)."""
    B, H, dq = q.shape
    S = k.shape[1]
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    impl = note_impl("flash_decode", impl or _resolve_impl(S, bs))
    if impl == "ref":
        return _ref.decode_attention(q, k, v, valid, scale)
    def kern(q, k, v, valid):
        acc, m, l = decode_attention_splits(q, k, v, valid, scale=scale,
                                            bs=min(bs, S),
                                            interpret=(impl == "interpret"))
        o = _merge(acc, m, l)                                # (B,Hkv,g,dv)
        return o.reshape(q.shape[0], q.shape[1], -1).astype(q.dtype)

    heads = ("batch", None, "model", None)
    return per_device(kern, (q, k, v, valid),
                      (("batch", "model", None), heads, heads,
                       ("batch", None)), ("batch", "model", None))
