"""Pallas TPU kernel for the RWKV-6 recurrence (chunked linear attention).

Chunked formulation per (batch, head, chunk) with the inter-chunk state S
carried in VMEM scratch across the chunk grid dimension:

  c_i   = cumsum_j<=i log w_j                      (per-channel, fp32)
  y_i   = (r_i * exp(c_{i-1}))^T S0                 [inter-chunk]
        + sum_{j<i} (sum_n r_in k_jn e^{c_{i-1,n}-c_{j,n}}) v_j
        + (r_i . (u*k_i)) v_i                       [intra-chunk]
  S'    = exp(c_L) * S0 + (k * exp(c_L - c))^T V    [state update]

All exponents are <= 0 (c is non-increasing), so the kernel is numerically
stable without clamping — this is why the intra-chunk attention uses an
explicit (L, L, N) per-channel decay tensor (1 MB VMEM at L=N=64) instead of
the overflow-prone exp(-c) factorization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
                 S_scr, *, L, N, nc):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        S_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (L, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, N)
    S0 = S_scr[...]                              # (N, N)

    lw = jnp.log(jnp.maximum(w, 1e-38))
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # c_i as a lower-triangular matmul: Mosaic has no cumsum lowering
    cum = jax.lax.dot_general(
        (jj <= ii).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # (L, N) c_i
    cum_prev = cum - lw                          # c_{i-1}

    q = r * jnp.exp(cum_prev)                    # (L, N), exp <= 1... <= e^0
    y_inter = jax.lax.dot_general(
        q, S0, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # intra-chunk: att_ij = sum_n r_in k_jn exp(c_{i-1,n} - c_{j,n}), j < i
    dec = jnp.exp(cum_prev[:, None, :] - cum[None, :, :])   # (L, L, N)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * dec, axis=-1)
    att = jnp.where(jj < ii, att, 0.0)
    diag = jnp.sum(r * u * k, axis=-1)                      # (L,)
    y_intra = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + diag[:, None] * v

    y_ref[0, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update (exponents <= 0)
    cl = cum[L - 1]                                          # (N,)
    ke = k * jnp.exp(cl[None, :] - cum)                      # (L, N)
    S_new = jnp.exp(cl)[:, None] * S0 + jax.lax.dot_general(
        ke, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    S_scr[...] = S_new

    @pl.when(c == nc - 1)
    def _final():
        sf_ref[0, 0] = S_new.astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state=None, *, chunk=64, interpret=False):
    """r,k,v,w: (B,T,H,N); u: (H,N); state: (B,H,N,N) or None.

    ``u`` travels as (H, 1, N) so its (1, 1, N) block obeys the TPU (8, 128)
    tiling rule (a (1, N) block of an (H, N) array does not).

    Returns (y (B,T,H,N), final_state (B,H,N,N)).
    """
    B, T, H, N = r.shape
    L = min(chunk, T)
    assert T % L == 0, "chunk must divide T"
    nc = T // L
    if state is None:
        state = jnp.zeros((B, H, N, N), jnp.float32)

    rt, kt, vt, wt = (x.transpose(0, 2, 1, 3) for x in (r, k, v, w))
    kernel = functools.partial(_wkv6_kernel, L=L, N=N, nc=nc)
    y, sf = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u[:, None, :], state)
    return y.transpose(0, 2, 1, 3), sf
