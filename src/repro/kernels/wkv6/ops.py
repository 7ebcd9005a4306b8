"""Public RWKV-6 recurrence op with backend dispatch.

impl resolution (env ``REPRO_WKV_IMPL`` overrides):
  * 'pallas'   : chunked Pallas TPU kernel (forward).
  * 'xla'      : chunked jnp implementation mirroring the kernel math
                 (lax.scan over chunks) — matmul-heavy, differentiable,
                 used for CPU/GPU and all dry-run lowering.
  * 'ref'      : exact sequential scan oracle (small shapes).
  * 'interpret': Pallas kernel under interpret=True (kernel tests).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import note_impl, per_device
from repro.kernels.wkv6 import ref as _ref
from repro.kernels.wkv6.wkv6 import wkv6 as _pallas_wkv6


def _resolve_impl(T: int, chunk: int) -> str:
    impl = os.environ.get("REPRO_WKV_IMPL", "")
    if impl:
        return impl
    if jax.default_backend() == "tpu":
        return "pallas"
    if T <= 2 * chunk or T % chunk:
        return "ref"
    return "xla"


def wkv6(r, k, v, w, u, state=None, *, chunk=64, impl=None):
    """r,k,v,w: (B,T,H,N); u: (H,N) -> (y, final_state (B,H,N,N))."""
    T = r.shape[1]
    impl = note_impl("wkv6", impl or _resolve_impl(T, chunk))
    if impl == "ref":
        return _ref.wkv6(r, k, v, w, u, state)
    if impl in ("pallas", "interpret"):
        B, _, H, N = r.shape
        if state is None:
            state = jnp.zeros((B, H, N, N), jnp.float32)

        def kern(r, k, v, w, u, state):
            return _pallas_wkv6(r, k, v, w, u, state, chunk=min(chunk, T),
                                interpret=(impl == "interpret"))
        seq = ("batch", None, "model", None)
        st = ("batch", "model", None, None)
        return per_device(kern, (r, k, v, w, u, state),
                          (seq,) * 4 + (("model", None), st), [seq, st])
    return _chunked(r, k, v, w, u, state, chunk=chunk)


def _chunked(r, k, v, w, u, state, *, chunk):
    """Chunked jnp mirror of the Pallas kernel (stable, differentiable)."""
    B, T, H, N = r.shape
    L = chunk
    nc = T // L
    if state is None:
        state = jnp.zeros((B, H, N, N), jnp.float32)
    uf = u.astype(jnp.float32)

    # (nc, B, H, L, N)
    def cm(x):
        return x.reshape(B, nc, L, H, N).transpose(1, 0, 3, 2, 4).astype(jnp.float32)

    rc, kc, vc, wc = cm(r), cm(k), cm(v), cm(w)

    def step(S, xs):
        rb, kb, vb, wb = xs                      # (B, H, L, N)
        lw = jnp.log(jnp.maximum(wb, 1e-38))
        cum = jnp.cumsum(lw, axis=2)
        cum_prev = cum - lw
        q = rb * jnp.exp(cum_prev)
        y_inter = jnp.einsum("bhln,bhnm->bhlm", q, S)
        dec = jnp.exp(cum_prev[:, :, :, None, :] - cum[:, :, None, :, :])
        att = jnp.einsum("bhin,bhjn,bhijn->bhij", rb, kb, dec)
        ii = jnp.arange(L)[:, None]
        jj = jnp.arange(L)[None, :]
        att = jnp.where(jj < ii, att, 0.0)
        diag = jnp.einsum("bhln,hn->bhl", rb * kb, uf)
        y_intra = jnp.einsum("bhij,bhjm->bhim", att, vb) \
            + diag[..., None] * vb
        cl = cum[:, :, L - 1]
        ke = kb * jnp.exp(cl[:, :, None, :] - cum)
        S = jnp.exp(cl)[..., None] * S + jnp.einsum("bhln,bhlm->bhnm", ke, vb)
        return S, (y_inter + y_intra)

    final, ys = jax.lax.scan(step, state, (rc, kc, vc, wc))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(B, T, H, N).astype(r.dtype)
    return y, final
