"""Plain float32 reference of a pruned Qwen2-style decoder, and the seeded
weights that both it and the served program run.

Qwen2 (arXiv:2407.10671): pre-RMSNorm blocks, grouped-query attention with
biases on q, k and v, rotary positions, a SiLU-gated MLP, tied embeddings.
At CORP's pruned shapes each head keeps ``qk_kept`` of its q/k dims as whole
rotary pairs, each with its own published frequency, and the logit scale
stays 1/sqrt(d_head): CORP folds its compensation into W_q and W_k.

Departures, all shared with the program: rotary pairs are interleaved
(dims 2i, 2i+1) rather than split in halves, a fixed permutation of the
same weights. Nothing here imports the program; the weights are laid out
under the names the program reads (``seg0/p0`` holds the stacked layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init(seed: int, m: dict):
    """Weights from ``seed`` for the sizes ``m`` (a configuration's
    ``model`` group), made on the device in one jitted call."""
    return _init(jax.random.key(seed % (2 ** 32)), tuple(sorted(m.items())))


@functools.partial(jax.jit, static_argnums=1)
def _init(key, items):
    m = dict(items)
    dt = jnp.dtype(m["dtype"])
    L, D, H, Hkv = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    dq, dv, F = m["qk_kept"], m["d_head"], m["d_ff_kept"]
    V = -(-m["vocab_size"] // 128) * 128
    ks = iter(jax.random.split(key, 16))
    full = 1.0 / (m["rope_theta"] ** (np.arange(0, dv, 2) / dv))
    # every kv head keeps its own dq/2 of the dv/2 rotary pairs; its q heads
    # share them
    pick = jax.vmap(lambda k: jnp.sort(jax.random.permutation(
        k, dv // 2)[:dq // 2]))(jax.random.split(next(ks), L * Hkv))
    inv_k = jnp.asarray(full, jnp.float32)[pick].reshape(L, Hkv, dq // 2)
    inv_q = jnp.repeat(inv_k, H // Hkv, axis=1)

    def norm(k, *shape):
        return 1.0 + _normal(k, shape, 0.1, jnp.float32)

    layers = {
        "ln1": {"scale": norm(next(ks), L, D)},
        "ln2": {"scale": norm(next(ks), L, D)},
        "mixer": {
            "wq": _normal(next(ks), (L, D, H, dq), D ** -0.5, dt),
            "wk": _normal(next(ks), (L, D, Hkv, dq), D ** -0.5, dt),
            "wv": _normal(next(ks), (L, D, Hkv, dv), D ** -0.5, dt),
            "wo": _normal(next(ks), (L, H, dv, D), (H * dv) ** -0.5, dt),
            "bq": _normal(next(ks), (L, H, dq), 0.1, jnp.float32),
            "bk": _normal(next(ks), (L, Hkv, dq), 0.1, jnp.float32),
            "bv": _normal(next(ks), (L, Hkv, dv), 0.1, jnp.float32),
            "rope_inv_q": inv_q, "rope_inv_k": inv_k},
        "mlp": {
            "wg": _normal(next(ks), (L, D, F), D ** -0.5, dt),
            "wu": _normal(next(ks), (L, D, F), D ** -0.5, dt),
            "wd": _normal(next(ks), (L, F, D), F ** -0.5, dt)},
    }
    return {"embed": _normal(next(ks), (V, D), 0.02, dt),
            "final_norm": {"scale": norm(next(ks), D)},
            "seg0": {"p0": layers}}


def _fp8(w):
    """``w`` rounded to float8 e4m3 with one scale per tensor: the control's
    precision, one step below the configuration's bfloat16."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w)) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, inv):
    """x: (T, H, d) with pairs (2i, 2i+1); inv: (H, d/2)."""
    ang = pos[:, None, None].astype(jnp.float32) * inv[None]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(3, 4))
def logits_rows(w, tokens, rows, items, fp8=False):
    """Logits (R, vocab) at positions ``rows`` of the causal forward over
    ``tokens`` (T,), in float32 at the highest matmul precision. ``fp8``
    rounds every weight matrix to float8 first: the control."""
    m = dict(items)
    f32 = jnp.float32
    q_of = _fp8 if fp8 else (lambda a: a.astype(f32))
    eps, H, Hkv = m["norm_eps"], m["n_heads"], m["n_kv_heads"]
    scale = m["d_head"] ** -0.5
    T = tokens.shape[0]
    pos = jnp.arange(T)
    emb = q_of(w["embed"])
    x = emb[tokens]
    dot = functools.partial(jnp.einsum, precision=HIGHEST)

    def layer(x, p):
        a, mx = p["ln1"], p["mixer"]
        h = _rms(x, a["scale"], eps)
        q = dot("td,dhq->thq", h, q_of(mx["wq"])) + mx["bq"]
        k = dot("td,dhq->thq", h, q_of(mx["wk"])) + mx["bk"]
        v = dot("td,dhv->thv", h, q_of(mx["wv"])) + mx["bv"]
        q = _rope(q, pos, mx["rope_inv_q"])
        k = jnp.repeat(_rope(k, pos, mx["rope_inv_k"]), H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)

        def block(qb_start):
            qb = jax.lax.dynamic_slice_in_dim(q, qb_start, 512, 0)
            s = dot("thq,shq->hts", qb, k) * scale
            keep = (qb_start + jnp.arange(512))[:, None] >= pos[None, :]
            s = jnp.where(keep[None], s, -jnp.inf)
            return dot("hts,shv->thv", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, jnp.arange(0, T, 512)).reshape(T, H, -1)
        x = x + dot("thv,hvd->td", o, q_of(mx["wo"]))
        h = _rms(x, p["ln2"]["scale"], eps)
        g = jax.nn.silu(dot("td,df->tf", h, q_of(p["mlp"]["wg"])))
        u = dot("td,df->tf", h, q_of(p["mlp"]["wu"]))
        return x + dot("tf,fd->td", g * u, q_of(p["mlp"]["wd"])), None

    x, _ = jax.lax.scan(layer, x, w["seg0"]["p0"])
    x = _rms(x[rows], w["final_norm"]["scale"], eps)
    return dot("rd,vd->rv", x, emb[:m["vocab_size"]])


def gaps(w, m: dict, prompt, served, fp8=False):
    """Per served position, how far below the reference's best logit the
    chosen token's logit lies: the served tokens', or with ``fp8`` the
    tokens the float8 control puts first. Returns a float64 array (G,)."""
    items = tuple(sorted(m.items()))
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    T = len(seq)
    Tp = -(-T // 1024) * 1024         # a few padded lengths, few compiles
    toks = np.zeros((Tp,), np.int32)
    toks[:T] = seq
    rows = np.arange(len(prompt) - 1, T, dtype=np.int32)
    ref = np.asarray(logits_rows(w, jnp.asarray(toks), jnp.asarray(rows),
                                 items), np.float64)
    if fp8:
        low = np.asarray(logits_rows(w, jnp.asarray(toks), jnp.asarray(rows),
                                     items, True))
        chosen = np.argmax(low, -1)
    else:
        chosen = np.asarray(served)
    return ref.max(-1) - ref[np.arange(len(rows)), chosen]
