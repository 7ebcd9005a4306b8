"""Decoder-only language model with scan-over-layers depth layout.

Depth is partitioned by ``cfg.layout()`` into scanned segments (stacked
params, one compile per repeating super-block) and unrolled remainder layers.
Supports the stub VLM frontend (precomputed patch embeddings prepended to the
token stream) per the assignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distrib.sharding import constrain
from repro.models import blocks as blk
from repro.models.common import dtype_of, embed_init, init_norm, apply_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

import os

_NESTED_REMAT = os.environ.get("REPRO_NESTED_REMAT", "0") == "1"


def _seg_name(si: int) -> str:
    return f"seg{si}"


def init_lm(key, cfg):
    ks = jax.random.split(key, 4 + cfg.n_layers)
    params = {"embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model),
                                  dtype_of(cfg)),
              "final_norm": init_norm(ks[1], cfg)}
    if not cfg.tie_embeddings:
        params["head"] = embed_init(ks[2], (cfg.d_model, cfg.padded_vocab),
                                    dtype_of(cfg))
    ki = 3
    for si, seg in enumerate(cfg.layout()):
        if seg[0] == "unroll":
            layers = {}
            for j, li in enumerate(seg[1]):
                kind, moe = cfg.layer_spec(li)
                dense_ff = cfg.eff_dense_d_ff if (cfg.moe is not None
                                                  and not moe
                                                  and cfg.dense_d_ff) else None
                layers[f"l{j}"] = blk.init_block(ks[ki + li], cfg, kind, moe,
                                                 dense_ff=dense_ff)
            params[_seg_name(si)] = layers
        else:
            _, reps, idxs = seg
            # stacked params: init each position once, tile via vmap over keys
            def init_pos(pos_key, li):
                kind, moe = cfg.layer_spec(li)
                return blk.init_block(pos_key, cfg, kind, moe)
            stacked = {}
            for j, li in enumerate(idxs):
                pos_keys = jax.random.split(
                    jax.random.fold_in(ks[ki], li), reps)
                stacked[f"p{j}"] = jax.vmap(
                    functools.partial(init_pos, li=li))(pos_keys)
            params[_seg_name(si)] = stacked
    return params


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill / calibration)
# ---------------------------------------------------------------------------

def _run_segments(params, x, cfg, *, positions, taps, train, mask_kind="causal",
                  mem=None, remat=False):
    aux_total = jnp.zeros((), jnp.float32)
    for si, seg in enumerate(cfg.layout()):
        name = _seg_name(si)
        if seg[0] == "unroll":
            for j, li in enumerate(seg[1]):
                kind, moe = cfg.layer_spec(li)
                t = {} if taps is not None else None
                x, aux = blk.apply_block(params[name][f"l{j}"], x, cfg, kind,
                                         moe, positions=positions, taps=t,
                                         mask_kind=mask_kind, mem=mem,
                                         train=train)
                x = constrain(x, "residual")
                aux_total = aux_total + aux
                if taps is not None:
                    for k, v in t.items():
                        taps[f"{name}/l{j}/{k}"] = v
        else:
            _, reps, idxs = seg
            specs = [cfg.layer_spec(li) for li in idxs]

            def one_layer(pj, x, positions, mem, *, kind, moe):
                y, aux = blk.apply_block(pj, x, cfg, kind, moe,
                                         positions=positions, taps=None,
                                         mask_kind=mask_kind, mem=mem,
                                         train=train)
                return constrain(y, "residual"), aux

            def body(carry, pslice):
                x = carry
                aux_g = jnp.zeros((), jnp.float32)
                ys = {}
                for j, (kind, moe) in enumerate(specs):
                    if taps is None and remat and _NESTED_REMAT:
                        # nested per-layer remat (§Perf iteration J2):
                        # REFUTED on the CPU-backend measurement — the
                        # backward transient did not shrink and recompute
                        # flops rose 19%; kept behind a flag for real-TPU
                        # re-evaluation (EXPERIMENTS.md §Perf).
                        fn = functools.partial(one_layer, kind=kind, moe=moe)
                        x, aux = jax.checkpoint(fn)(pslice[f"p{j}"], x,
                                                    positions, mem)
                        aux_g = aux_g + aux
                        continue
                    t = {} if taps is not None else None
                    x, aux = blk.apply_block(pslice[f"p{j}"], x, cfg, kind,
                                             moe, positions=positions, taps=t,
                                             mask_kind=mask_kind, mem=mem,
                                             train=train)
                    x = constrain(x, "residual")
                    aux_g = aux_g + aux
                    if taps is not None:
                        for k, v in t.items():
                            ys[f"p{j}/{k}"] = v
                return x, (aux_g, ys)

            if remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, (aux_g, ys) = jax.lax.scan(body, x, params[name])
            aux_total = aux_total + jnp.sum(aux_g)
            if taps is not None:
                for k, v in ys.items():
                    taps[f"{name}/{k}"] = v   # stacked (reps, ...)
    return x, aux_total


def apply_lm(params, tokens, cfg, *, taps=None, patch_embeds=None,
             train=False, remat=None):
    """tokens: (B, T) int32; patch_embeds: (B, P, D) optional (VLM stub).

    Returns (logits (B, T_total, padded_vocab), aux_loss).
    """
    x = params["embed"][tokens]
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = constrain(x, "residual")
    remat = train if remat is None else remat
    x, aux = _run_segments(params, x, cfg, positions=positions, taps=taps,
                           train=train, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    logits = constrain(x @ head, "logits")
    return logits, aux


def lm_loss(params, batch, cfg, *, train=True):
    """batch: {'tokens': (B,T), 'labels': (B,T)} -> scalar loss (fp32)."""
    logits, aux = apply_lm(params, batch["tokens"], cfg,
                           patch_embeds=batch.get("patch_embeds"),
                           train=train)
    labels = batch["labels"]
    if "patch_embeds" in batch:
        logits = logits[:, batch["patch_embeds"].shape[1]:]
    lf = logits.astype(jnp.float32)
    # mask padded vocab entries
    if cfg.padded_vocab != cfg.vocab_size:
        neg = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e30, jnp.float32)
        lf = jnp.concatenate(
            [lf[..., :cfg.vocab_size],
             jnp.broadcast_to(neg, lf.shape[:-1] + neg.shape)], axis=-1)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return nll + 0.01 * aux


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode
# ---------------------------------------------------------------------------

def init_lm_cache(cfg, batch: int, max_len: int):
    caches = {"pos": jnp.zeros((batch,), jnp.int32)}
    for si, seg in enumerate(cfg.layout()):
        name = _seg_name(si)
        if seg[0] == "unroll":
            caches[name] = {
                f"l{j}": blk.init_block_cache(cfg, cfg.layer_spec(li)[0],
                                              batch, max_len)
                for j, li in enumerate(seg[1])}
        else:
            _, reps, idxs = seg
            def tile(tree, reps=reps):
                return jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape), tree)
            caches[name] = {
                f"p{j}": tile(blk.init_block_cache(cfg, cfg.layer_spec(li)[0],
                                                   batch, max_len))
                for j, li in enumerate(idxs)}
    return caches


def lm_decode_step(params, token, cache, cfg, count_lanes=None):
    """token: (B, 1) int32. Returns (logits (B,1,V), new_cache), and with
    ``count_lanes`` ((B,) bool: the lanes that hold a request) the MoE
    counters of those lanes summed over the layers, int32 (2,):
    (token, held-expert) pairs and held experts that got one."""
    x = params["embed"][token]
    new_cache = {"pos": cache["pos"] + 1}
    total = jnp.zeros((2,), jnp.int32)

    def lane_counts():
        return None if count_lanes is None else {"lanes": count_lanes}

    def moe_counts(c):
        return (c or {}).get("moe", jnp.zeros((2,), jnp.int32))

    for si, seg in enumerate(cfg.layout()):
        name = _seg_name(si)
        if seg[0] == "unroll":
            nc = {}
            for j, li in enumerate(seg[1]):
                kind, moe = cfg.layer_spec(li)
                c = lane_counts()
                x, nc[f"l{j}"] = blk.decode_block(
                    params[name][f"l{j}"], x, cache[name][f"l{j}"], cfg,
                    kind, moe, counts=c)
                total = total + moe_counts(c)
            new_cache[name] = nc
        else:
            _, reps, idxs = seg
            specs = [cfg.layer_spec(li) for li in idxs]

            # the stacked cache rides in the carry, so each layer writes
            # its rows in place and the kernel reads the stack where it
            # lies; passed as scan xs/ys, every layer would be sliced out
            # and restacked into a fresh buffer
            def body(carry, xs):
                x, cseg, total = carry
                pslice, layer = xs
                cseg = dict(cseg)
                for j, (kind, moe) in enumerate(specs):
                    c = lane_counts()
                    x, cseg[f"p{j}"] = blk.decode_block(
                        pslice[f"p{j}"], x, cseg[f"p{j}"], cfg, kind, moe,
                        layer=layer, counts=c)
                    total = total + moe_counts(c)
                return (x, cseg, total), None

            (x, new_cache[name], total), _ = jax.lax.scan(
                body, (x, cache[name], total),
                (params[name], jnp.arange(reps, dtype=jnp.int32)))
    x = apply_norm(params["final_norm"], x, cfg)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    if count_lanes is not None:
        return x @ head, new_cache, total
    return x @ head, new_cache


def lm_prefill(params, tokens, cfg, max_len: int, patch_embeds=None,
               lengths=None):
    """Prefill: full forward returning (last-token logits, populated cache).

    Implemented as full-sequence attention + cache writeback per layer; for
    the dry-run shapes this is the cheapest correct formulation (one pass).

    ``lengths`` (B,) int32 enables *ragged* prefill on right-padded token
    batches: logits are gathered at position ``lengths-1`` per sample and
    every cache ``pos`` is set to ``lengths``, so padded tail positions are
    never read back (causality keeps rows < lengths exact). Only valid for
    pure global-attention stacks — sliding-window ring buffers and recurrent
    (mamba/rwkv) states are contaminated by pad tokens.
    """
    if lengths is not None and set(cfg.layer_kinds) != {"attn"}:
        raise ValueError("ragged prefill (lengths=) requires a pure "
                         f"global-attention stack, got {set(cfg.layer_kinds)}")
    B, T = tokens.shape
    x = params["embed"][tokens]
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        T = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    cache = {"pos": jnp.full((B,), T, jnp.int32) if lengths is None
             else lengths.astype(jnp.int32)}
    # sequence-parallel residual (§Perf iteration D1): turns the row-parallel
    # output-projection all-reduces into reduce-scatter/all-gather pairs and
    # keeps every (B,T,D) buffer sequence-sharded
    x = constrain(x, "residual")

    def run_layer(p, x, kind, moe):
        h = apply_norm(p["ln1"], x, cfg)
        from repro.models import attention as attn_mod
        from repro.models import mlp as mlp_mod
        from repro.models import ssm as ssm_mod
        if kind in ("attn", "swa"):
            y, c = attn_mod.apply_attn(p["mixer"], h, cfg, kind,
                                       positions=positions, return_cache=True)
            if kind == "swa":
                c = _window_cache(c, cfg, max_len)
            else:
                c = _pad_cache(c, max_len)
        elif kind == "mamba":
            y, c = ssm_mod.apply_mamba(p["mixer"], h, cfg)
        else:
            y, c = ssm_mod.apply_rwkv_time(p["mixer"], h, cfg)
            c = {"time": c}
        x = x + y
        h = apply_norm(p["ln2"], x, cfg)
        if kind == "rwkv":
            y, cs = ssm_mod.apply_rwkv_channel(p["mlp"], h, cfg)
            c["channel"] = cs
        elif moe:
            y, _ = mlp_mod.apply_moe(p["mlp"], h, cfg)
        else:
            y = mlp_mod.apply_mlp(p["mlp"], h, cfg)
        return constrain(x + y, "residual"), c

    for si, seg in enumerate(cfg.layout()):
        name = _seg_name(si)
        if seg[0] == "unroll":
            cs = {}
            for j, li in enumerate(seg[1]):
                kind, moe = cfg.layer_spec(li)
                x, c = run_layer(params[name][f"l{j}"], x, kind, moe)
                cs[f"l{j}"] = c
            cache[name] = cs
        else:
            _, reps, idxs = seg
            specs = [cfg.layer_spec(li) for li in idxs]

            def body(carry, pslice):
                x = carry
                cs = {}
                for j, (kind, moe) in enumerate(specs):
                    x, c = run_layer(pslice[f"p{j}"], x, kind, moe)
                    cs[f"p{j}"] = c
                return x, cs

            x, cs = jax.lax.scan(body, x, params[name])
            cache[name] = cs
    if lengths is None:
        x_last = x[:, -1:]
    else:
        idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
        cache = override_cache_pos(cache, lengths)
    x = apply_norm(params["final_norm"], x_last, cfg)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    return x @ head, cache


def override_cache_pos(tree, lengths):
    """Set every ``pos`` leaf of a prefill cache to per-sample ``lengths``.

    Per-layer caches carry their own ``pos`` (the decode valid-length); for a
    ragged (right-padded) prefill they must all report the true length, not
    the padded one. Scanned-segment leaves are (reps, B) — broadcast covers
    both layouts.
    """
    if isinstance(tree, dict):
        return {k: (jnp.broadcast_to(lengths.astype(v.dtype), v.shape)
                    if k == "pos" else override_cache_pos(v, lengths))
                for k, v in tree.items()}
    return tree


def _pad_cache(c, max_len):
    """Right-pad a freshly built cache to max_len time slots."""
    out = dict(c)
    for key in ("k", "v", "lat"):
        if key in c:
            T = c[key].shape[1]
            if T < max_len:
                pad = [(0, 0)] * c[key].ndim
                pad[1] = (0, max_len - T)
                out[key] = jnp.pad(c[key], pad)
    return out


def _window_cache(c, cfg, max_len):
    """Convert a full prefill cache into the ring-buffer window cache."""
    W = min(cfg.sliding_window, max_len)
    T = c["k"].shape[1]
    B = c["k"].shape[0]
    n = min(W, T)
    keep_k = c["k"][:, T - n:]
    keep_v = c["v"][:, T - n:]
    pos_vals = jnp.arange(T - n, T, dtype=jnp.int32)
    slots = jnp.mod(pos_vals, W)
    k_ring = jnp.zeros((B, W) + c["k"].shape[2:], c["k"].dtype)
    v_ring = jnp.zeros((B, W) + c["v"].shape[2:], c["v"].dtype)
    k_ring = k_ring.at[:, slots].set(keep_k)
    v_ring = v_ring.at[:, slots].set(keep_v)
    abs_ring = jnp.full((B, W), -1, jnp.int32)
    abs_ring = abs_ring.at[:, slots].set(
        jnp.broadcast_to(pos_vals[None], (B, n)))
    return {"k": k_ring, "v": v_ring, "pos": c["pos"], "abs_pos": abs_ring}
