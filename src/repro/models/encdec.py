"""Encoder-decoder model (seamless-m4t family).

Encoder consumes precomputed frame embeddings (audio frontend is a stub per
the assignment); decoder is a causal LM with cross-attention to the encoder
memory. Sinusoidal absolute positions (no rope) — which makes the paper's
full-matrix QK compensation exactly applicable (DESIGN.md class-1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distrib.sharding import constrain
from repro.models import blocks as blk
from repro.models.common import apply_norm, dtype_of, embed_init, init_norm


def _sinusoid(T: int, D: int):
    pos = np.arange(T)[:, None]
    i = np.arange(D // 2)[None, :]
    ang = pos / (10000 ** (2 * i / D))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


def init_encdec(key, cfg):
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    params = {
        "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dt),
        "enc_final_norm": init_norm(ks[1], cfg),
        "final_norm": init_norm(ks[2], cfg),
        "head": embed_init(ks[3], (cfg.d_model, cfg.padded_vocab), dt),
    }

    def init_enc(k):
        return blk.init_block(k, cfg, "attn", False)

    def init_dec(k):
        return blk.init_block(k, cfg, "attn", False, cross=True)

    params["enc"] = {"p0": jax.vmap(init_enc)(
        jax.random.split(ks[4], cfg.n_enc_layers))}
    params["dec"] = {"p0": jax.vmap(init_dec)(
        jax.random.split(ks[5], cfg.n_layers))}
    return params


def _run_stack(stack, x, cfg, *, positions, taps, mask_kind, mem, prefix,
               train, remat=False):
    specs = [("attn", False)]

    def body(carry, pslice):
        x = carry
        t = {} if taps is not None else None
        x, _ = blk.apply_block(pslice["p0"], x, cfg, "attn", False,
                               positions=positions, taps=t,
                               mask_kind=mask_kind, mem=mem, train=train)
        x = constrain(x, "residual")
        return x, (t or {})

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, ys = jax.lax.scan(body, x, stack)
    if taps is not None:
        for k, v in ys.items():
            taps[f"{prefix}/p0/{k}"] = v
    return x


def encode(params, frames, cfg, *, taps=None, train=False, remat=False):
    """frames: (B, S, D) stub frontend embeddings -> encoder memory."""
    B, S, D = frames.shape
    x = frames.astype(dtype_of(cfg)) + _sinusoid(S, D).astype(dtype_of(cfg))
    x = constrain(x, "residual")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _run_stack(params["enc"], x, cfg, positions=positions, taps=taps,
                   mask_kind="full", mem=None, prefix="enc", train=train,
                   remat=remat)
    return apply_norm(params["enc_final_norm"], x, cfg)


def apply_encdec(params, frames, tokens, cfg, *, taps=None, train=False,
                 remat=None):
    """Returns (logits (B, T, padded_vocab), aux)."""
    remat = train if remat is None else remat
    mem = encode(params, frames, cfg, taps=taps, train=train, remat=remat)
    B, T = tokens.shape
    x = params["embed"][tokens]
    x = x + _sinusoid(T, cfg.d_model).astype(x.dtype)
    x = constrain(x, "residual")
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = _run_stack(params["dec"], x, cfg, positions=positions, taps=taps,
                   mask_kind="causal", mem=mem, prefix="dec", train=train,
                   remat=remat)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = constrain(x @ params["head"], "logits")
    return logits, jnp.zeros((), jnp.float32)


def encdec_loss(params, batch, cfg, *, train=True):
    """batch: {'frames': (B,S,D), 'tokens': (B,T), 'labels': (B,T)}."""
    logits, _ = apply_encdec(params, batch["frames"], batch["tokens"], cfg,
                             train=train)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def encdec_prefill(params, frames, tokens, cfg, max_len: int, lengths=None):
    """Encode + teacher-forced decoder prefill. Returns (logits, cache).

    ``lengths`` (B,) enables ragged decoder prompts (right-padded tokens):
    causal self-attention keeps decoder cache rows < lengths exact, and
    cross-attention/MLP are per-position, so only the logits gather and the
    cache ``pos`` need the true length. ``frames`` must be unpadded — the
    encoder memory is attended in full.
    """
    from repro.models import attention as attn_mod
    from repro.models import lm as lm_mod
    mem = encode(params, frames, cfg)
    B, T = tokens.shape
    x = params["embed"][tokens]
    x = x + _sinusoid(T, cfg.d_model).astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    caches = []
    p_stack = params["dec"]["p0"]
    n = cfg.n_layers

    def one_layer(p, x):
        h = apply_norm(p["ln1"], x, cfg)
        y, c = attn_mod.apply_attn(p["mixer"], h, cfg, "attn",
                                   positions=positions, return_cache=True)
        c = lm_mod._pad_cache(c, max_len)
        x = x + y
        h = apply_norm(p["ln_cross"], x, cfg)
        x = x + attn_mod.apply_cross_attn(p["cross"], h, mem, cfg)
        h = apply_norm(p["ln2"], x, cfg)
        from repro.models import mlp as mlp_mod
        x = x + mlp_mod.apply_mlp(p["mlp"], h, cfg)
        cc = attn_mod.precompute_cross_cache(p["cross"], mem, cfg)
        return x, {"self": c, "cross": cc}

    def body(carry, pslice):
        x = carry
        x, c = one_layer(pslice, x)
        return x, c

    x, cache_stack = jax.lax.scan(body, x, p_stack)
    if lengths is None:
        x_last = x[:, -1:]
        pos = jnp.full((B,), T, jnp.int32)
    else:
        idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
        cache_stack = lm_mod.override_cache_pos(cache_stack, lengths)
        pos = lengths.astype(jnp.int32)
    x = apply_norm(params["final_norm"], x_last, cfg)
    return x @ params["head"], {"dec": cache_stack, "pos": pos}


def encdec_decode_step(params, token, cache, cfg):
    from repro.models import attention as attn_mod
    x = params["embed"][token]
    pos = cache["pos"]
    # absolute sinusoidal position of the new token
    D = cfg.d_model
    i = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    ang = pos.astype(jnp.float32)[:, None] / (10000.0 ** (2 * i / D))
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)  # (B, D)
    x = x + pe[:, None, :].astype(x.dtype)

    # the decoder's stacked caches ride in the carry and are read in place
    # at the layer index (lm.lm_decode_step)
    def body(carry, xs):
        x, cstack = carry
        pslice, layer = xs
        h = apply_norm(pslice["ln1"], x, cfg)
        y, c_new = attn_mod.decode_attn(pslice["mixer"], h, cstack["self"],
                                        cfg, "attn", layer=layer)
        x = x + y
        h = apply_norm(pslice["ln_cross"], x, cfg)
        x = x + attn_mod.decode_cross_attn(pslice["cross"], h,
                                           cstack["cross"], cfg, layer=layer)
        h = apply_norm(pslice["ln2"], x, cfg)
        from repro.models import mlp as mlp_mod
        x = x + mlp_mod.apply_mlp(pslice["mlp"], h, cfg)
        return (x, {"self": c_new, "cross": cstack["cross"]}), None

    (x, new_stack), _ = jax.lax.scan(
        body, (x, cache["dec"]),
        (params["dec"]["p0"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = apply_norm(params["final_norm"], x, cfg)
    return x @ params["head"], {"dec": new_stack, "pos": pos + 1}
