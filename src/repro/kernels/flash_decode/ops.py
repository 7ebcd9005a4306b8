"""Public flash-decoding op: split-KV kernel + logsumexp merge epilogue.

impl resolution (env ``REPRO_DECODE_IMPL`` overrides): 'pallas' on TPU,
'ref' elsewhere, 'interpret' for kernel tests.

Every call records in ``kernels.RESOLVED`` how the cache reached the
attention, once per layer it covers: ``decode_cache:in_place`` when the
kernel read the stored stack by layer index, ``decode_cache:sliced`` when
the layer was cut out of it first (the jnp reference).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import note_impl, per_device
from repro.kernels.flash_decode import ref as _ref
from repro.kernels.flash_decode.flash_decode import (decode_attention_splits,
                                                     decode_latent_attention)


def _block_rows(S: int) -> int:
    """Cache rows per kernel block: 1024 where S holds two such blocks,
    else 512 (all of a shorter cache). At the chat cell's shapes on a v5e
    1024-row blocks walked the stacked cache 19% faster than 512 (8.49
    against 10.53 ms for 28 layers), 2048 and 4096 no faster."""
    return 1024 if S % 1024 == 0 and S >= 2048 else min(512, S)


def _resolve_impl(S: int, bs: int) -> str:
    impl = os.environ.get("REPRO_DECODE_IMPL", "")
    if impl:
        return impl
    if jax.default_backend() == "tpu" and S % bs == 0 and S >= 2 * bs:
        return "pallas"
    return "ref"


def _merge(acc, m, l):
    """Logsumexp-merge per-split partials over the split axis (ns)."""
    m_max = jnp.max(m, axis=1, keepdims=True)                # (B,1,H,1)
    corr = jnp.exp(m - m_max)
    l_tot = jnp.sum(l * corr, axis=1)                        # (B,H,1)
    acc_tot = jnp.sum(acc * corr, axis=1)                    # (B,H,dv)
    return acc_tot / jnp.maximum(l_tot, 1e-30)


def layer_rows(k, layer, heads: int):
    """One layer of a stacked cache (L,B,S,Hkv*d) as (B,S,Hkv,d): a slice."""
    kl = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
    return kl.reshape(kl.shape[:2] + (heads, -1))


def decode_attention(q, k, v, valid, *, layer=None, scale=None, bs=None,
                     impl=None):
    """q: (B,H,dq); valid: (B,S) -> (B,H,dv).

    k/v: a stacked cache (L,B,S,Hkv*d) read at ``layer``, or, with
    ``layer`` None, one layer in the per-head layout (B,S,Hkv,d).
    """
    B, H, dq = q.shape
    if layer is None:                         # a stack of one layer
        k = k.reshape((1,) + k.shape[:2] + (-1,))
        v = v.reshape((1,) + v.shape[:2] + (-1,))
        layer = 0
    S = k.shape[2]
    bs = bs or _block_rows(S)
    Hkv = k.shape[-1] // dq
    if scale is None:
        scale = float(1.0 / (dq ** 0.5))
    impl = note_impl("flash_decode", impl or _resolve_impl(S, bs))
    note_impl("decode_cache", "sliced" if impl == "ref" else "in_place",
              n=k.shape[0])
    if impl == "ref":
        return _ref.decode_attention(q, layer_rows(k, layer, Hkv),
                                     layer_rows(v, layer, Hkv), valid, scale)

    def kern(qg, k, v, valid, layer):
        acc, m, l = decode_attention_splits(
            qg.reshape(qg.shape[0], -1, dq), k, v, valid, layer[0],
            scale=scale, bs=min(bs, S), interpret=(impl == "interpret"))
        return _merge(acc, m, l).astype(q.dtype)            # (B,H,dv)

    # q travels grouped by kv head, so the model axis splits q heads and
    # the cache's folded kv heads together, on whole heads or not at all
    cache = (None, "batch", None, "model")
    return per_device(kern, (q.reshape(B, Hkv, -1, dq), k, v, valid,
                             jnp.asarray(layer, jnp.int32).reshape(1)),
                      (("batch", "model", None, None), cache, cache,
                       ("batch", None), (None,)), ("batch", "model", None))


def decode_latent(q, lat, last, *, layer, scale, dv, bs=None, impl=None):
    """MLA's absorbed decode attention. q: (B,H,C) = [q_nope·W_uk |
    q_rope]; lat: the stacked latent cache (L,B,S,C) = [ckv | k_rope],
    read at ``layer``; last: (B,) the last valid row -> (B,H,dv), the
    attention-weighted sum of each row's first ``dv`` lanes."""
    S = lat.shape[2]
    bs = bs or _block_rows(S)
    impl = note_impl("flash_decode", impl or _resolve_impl(S, bs))
    note_impl("decode_cache", "sliced" if impl == "ref" else "in_place",
              n=lat.shape[0])
    if impl == "ref":
        one = jax.lax.dynamic_index_in_dim(lat, layer, 0, keepdims=False)
        return _ref.decode_latent(q, one, last, scale, dv)

    def kern(q, lat, last, layer):
        return decode_latent_attention(q, lat, last, layer[0], scale=scale,
                                       dv=dv, bs=min(bs, S),
                                       interpret=(impl == "interpret"))

    # every head reads the whole latent row: lanes never split
    return per_device(kern, (q, lat, last,
                             jnp.asarray(layer, jnp.int32).reshape(1)),
                      (("batch", None, None), (None, "batch", None, None),
                       ("batch",), (None,)), ("batch", None, None))
