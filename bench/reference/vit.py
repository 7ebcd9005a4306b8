"""Plain float32 reference of a pruned DeiT, and the seeded weights that
both it and the program run.

DeiT (Touvron et al. 2021, arXiv:2012.12877): 16x16 patches embedded by a
linear map, a class token, learned positions, pre-LayerNorm blocks of
bidirectional multi-head attention and a two-matrix GELU MLP, the class
token's final norm into a linear head. At CORP's pruned shapes each head
keeps ``qk_kept`` of its q/k dims and the MLP ``d_ff_kept`` hidden units;
the logit scale stays 1/sqrt(d_head).

Departures, all shared with the program and stated in the configuration:
GELU in its tanh form, LayerNorm eps 1e-5. Nothing here imports the
program; the weights are laid out under the names the program reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# Every residual dimension of every token carries this constant, through
# the position embeddings. LayerNorm subtracts it exactly, so no output
# changes; but the residual stream then needs the float32 the
# configuration states, as the massive activations of trained ViTs make it
# (Sun et al. 2024, arXiv:2402.17762). Without it, float32 at default
# precision (one bf16 pass per matmul) and bfloat16 storage differ by
# less than twice in the logits, and no limit could tell them apart.
RESIDUAL_OFFSET = 16.0


def _normal(key, shape, scale, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init(seed: int, m: dict):
    """Weights from ``seed`` for the sizes ``m``, made on the device in one
    jitted call."""
    return _init(jax.random.key(seed % (2 ** 32)), tuple(sorted(m.items())))


@functools.partial(jax.jit, static_argnums=1)
def _init(key, items):
    m = dict(items)
    dt = jnp.dtype(m["dtype"])
    L, D, H = m["n_layers"], m["d_model"], m["n_heads"]
    dq, dv, F = m["qk_kept"], m["d_head"], m["d_ff_kept"]
    N = (m["img_size"] // m["patch"]) ** 2
    P = m["patch"] ** 2 * 3
    ks = iter(jax.random.split(key, 24))

    def ln(*lead):
        return {"scale": 1.0 + _normal(next(ks), lead + (D,), 0.1),
                "bias": _normal(next(ks), lead + (D,), 0.1)}

    layers = {
        "ln1": ln(L), "ln2": ln(L),
        "mixer": {
            "wq": _normal(next(ks), (L, D, H, dq), D ** -0.5, dt),
            "wk": _normal(next(ks), (L, D, H, dq), D ** -0.5, dt),
            "wv": _normal(next(ks), (L, D, H, dv), D ** -0.5, dt),
            "wo": _normal(next(ks), (L, H, dv, D), (H * dv) ** -0.5, dt),
            "bq": _normal(next(ks), (L, H, dq), 0.1),
            "bk": _normal(next(ks), (L, H, dq), 0.1),
            "bv": _normal(next(ks), (L, H, dv), 0.1)},
        "mlp": {
            "wu": _normal(next(ks), (L, D, F), D ** -0.5, dt),
            "bu": _normal(next(ks), (L, F), 0.1),
            "wd": _normal(next(ks), (L, F, D), F ** -0.5, dt),
            "bd": _normal(next(ks), (L, D), 0.1)},
    }
    return {"cls": _normal(next(ks), (1, 1, D), 0.02, dt),
            "pos": (_normal(next(ks), (1, N + 1, D), 0.02)
                    + RESIDUAL_OFFSET).astype(dt),
            "patch_w": _normal(next(ks), (P, D), P ** -0.5, dt),
            "patch_b": _normal(next(ks), (D,), 0.1),
            "final_norm": ln(),
            "class_head": _normal(next(ks), (D, m["n_classes"]), D ** -0.5,
                                  dt),
            "head_bias": _normal(next(ks), (m["n_classes"],), 0.1),
            "seg0": {"p0": layers}}


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def logits(w, images, items, dtype="float32"):
    """Class logits (B, n_classes) of ``images`` (B, H, W, 3). ``dtype``
    bfloat16 rounds weights, inputs and every activation to bfloat16: the
    control, one step below the configuration's float32."""
    m = dict(items)
    dt = jnp.dtype(dtype)
    prec = HIGHEST if dt == jnp.float32 else None
    w = jax.tree.map(lambda a: a.astype(dt), w)
    dot = functools.partial(jnp.einsum, precision=prec,
                            preferred_element_type=dt)
    eps, p = m["norm_eps"], m["patch"]
    B, Hi, Wi, C = images.shape
    x = images.astype(dt).reshape(B, Hi // p, p, Wi // p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, -1, p * p * C)
    x = dot("bnp,pd->bnd", x, w["patch_w"]) + w["patch_b"]
    cls = jnp.broadcast_to(w["cls"], (B, 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], 1) + w["pos"]
    scale = m["d_head"] ** -0.5

    def layer(x, lp):
        a = lp["mixer"]
        h = _ln(x, lp["ln1"], eps)
        q = dot("btd,dhq->bthq", h, a["wq"]) + a["bq"]
        k = dot("btd,dhq->bthq", h, a["wk"]) + a["bk"]
        v = dot("btd,dhv->bthv", h, a["wv"]) + a["bv"]
        s = dot("bthq,bshq->bhts", q, k) * scale
        o = dot("bhts,bshv->bthv", jax.nn.softmax(s, -1), v)
        x = x + dot("bthv,hvd->btd", o, a["wo"])
        h = _ln(x, lp["ln2"], eps)
        u = jax.nn.gelu(dot("btd,df->btf", h, lp["mlp"]["wu"])
                        + lp["mlp"]["bu"], approximate=True)
        return x + dot("btf,fd->btd", u, lp["mlp"]["wd"]) + lp["mlp"]["bd"], \
            None

    x, _ = jax.lax.scan(layer, x, w["seg0"]["p0"])
    x = _ln(x[:, 0], w["final_norm"], eps)
    return (dot("bd,dc->bc", x, w["class_head"])
            + w["head_bias"]).astype(jnp.float32)
