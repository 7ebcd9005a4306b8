"""MLP mixers: dense (plain / gated) and Mixture-of-Experts.

CORP integration: the tap ``h`` is the activation entering the *second*
linear map (Eq. 1 of the paper: ``y = W x + b`` with ``x`` the hidden
activation). For gated (GLU) MLPs the hidden activation is
``act(x W_g) * (x W_u)`` — pruning a hidden channel removes a column of both
W_g and W_u plus a row of W_d, exactly one structured unit. For MoE the tap
additionally carries the dispatch mask so statistics are expert-conditional.

MoE serving (``train=False``, no taps) is dropless: every token is routed
over every routed expert by the configured router (``route``), and the
layer computes the (token, expert) pairs whose expert it holds
(``cfg.moe.held`` from ``first_held``: an expert-parallel deployment's
share on one chip), sorted by expert through a grouped matmul
(``kernels/expert_gmm``). No token is dropped, so a token's output does
not depend on the batch it shares. Training and CORP's calibration taps
keep the GShard capacity dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import activation, dense_init, dtype_of, tap


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff=None, bias=None):
    dt = dtype_of(cfg)
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.eff_d_ff
    bias = cfg.mlp_kind == "plain" if bias is None else bias
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "glu":
        p = {
            "wg": dense_init(ks[0], (D, F), dt),
            "wu": dense_init(ks[1], (D, F), dt),
            "wd": dense_init(ks[2], (F, D), dt),
        }
    else:
        p = {
            "wu": dense_init(ks[0], (D, F), dt),
            "wd": dense_init(ks[1], (F, D), dt),
        }
    if bias:
        p["bu"] = jnp.zeros((F,), jnp.float32)
        p["bd"] = jnp.zeros((D,), jnp.float32)
        if cfg.mlp_kind == "glu":
            p["bg"] = jnp.zeros((F,), jnp.float32)
    return p


def apply_mlp(p, x, cfg, taps=None):
    """x: (..., D) -> (..., D)."""
    act = activation(cfg.act)
    dt = x.dtype
    u = x @ p["wu"]
    if "bu" in p:
        u = u + p["bu"].astype(dt)
    if "wg" in p:
        gpre = x @ p["wg"]
        if "bg" in p:
            gpre = gpre + p["bg"].astype(dt)
        h = act(gpre) * u
    else:
        h = act(u)
    tap(taps, "h", h)
    y = h @ p["wd"]
    if "bd" in p:
        y = y + p["bd"].astype(dt)
    return y


# ---------------------------------------------------------------------------
# Mixture of Experts: the router, the dropless held-expert layer (serving),
# and GShard-style grouped one-hot dispatch with capacity (training)
# ---------------------------------------------------------------------------

def init_moe(key, cfg):
    """Router over every routed expert (``eff_num_experts``, plus the
    sigmoid router's correction bias); weights of the held ones."""
    dt = dtype_of(cfg)
    m = cfg.moe
    D, E, Eh = cfg.d_model, cfg.eff_num_experts, cfg.held_experts
    F = cfg.eff_d_ff if cfg.d_ff_kept is not None else m.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=0.02),
        "wg": dense_init(ks[1], (Eh, D, F), dt),
        "wu": dense_init(ks[2], (Eh, D, F), dt),
        "wd": dense_init(ks[3], (Eh, F, D), dt),
    }
    if m.scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if m.num_shared > 0:
        # shared experts = one dense MLP of num_shared * d_expert hidden
        shared_cfg = cfg.replace(d_ff=m.num_shared * m.d_expert,
                                 d_ff_kept=(None if cfg.d_ff_kept is None
                                            else m.num_shared * cfg.d_ff_kept))
        p["shared"] = init_mlp(ks[4], shared_cfg)
    return p


def _group_tokens(x, target=2048):
    """(B, T, D) -> (G, tg, D) with tg <= target dividing B*T."""
    B, T, D = x.shape
    n = B * T
    tg = min(target, n)
    while n % tg:
        tg -= 1
    return x.reshape(n // tg, tg, D), n


def route(p, x, cfg):
    """x: (..., D) -> (gates (..., K) f32, experts (..., K) int32): each
    token's top-k over every routed expert.

    softmax: top-k of the softmax. sigmoid (DeepSeek-V3's noaux_tc):
    sigmoid scores; the correction bias ``router_bias`` is added only to
    choose; with ``n_group`` > 1 a group scores the sum of its top-2
    biased scores and a token chooses only within its ``topk_group`` best
    groups. Gates are the chosen unbiased scores, renormalised
    (``norm_topk``) and times ``routed_scale``.
    """
    m = cfg.moe
    with jax.named_scope("moe.route"):
        # float32 at full precision, as published: a routing choice turns
        # on small differences of the scores
        logits = jnp.dot(x.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if m.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        choice = scores + p["router_bias"] if "router_bias" in p else scores
        if m.n_group > 1:
            E = choice.shape[-1]
            grouped = choice.reshape(choice.shape[:-1]
                                     + (m.n_group, E // m.n_group))
            top2 = jax.lax.top_k(grouped, min(2, E // m.n_group))[0]
            _, gidx = jax.lax.top_k(top2.sum(-1), m.topk_group)
            keep = jax.nn.one_hot(gidx, m.n_group, dtype=bool).any(-2)
            choice = jnp.where(keep[..., None], grouped,
                               -jnp.inf).reshape(choice.shape)
        _, idx = jax.lax.top_k(choice, m.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        if m.norm_topk:
            gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True),
                                        1e-20)
    return gates * m.routed_scale, idx.astype(jnp.int32)


def _held_pairs(idx, cfg):
    """(key, sizes): each (token, k) pair's held expert, from 0, or the
    held count where its expert lies on another chip; and how many pairs
    each held expert got."""
    Eh = cfg.held_experts
    local = idx - cfg.moe.first_held
    key = jnp.where((local >= 0) & (local < Eh), local, Eh)
    sizes = jnp.sum(jax.nn.one_hot(key.reshape(-1), Eh, dtype=jnp.int32), 0)
    return key, sizes


def _apply_held(p, x, cfg, counts):
    """Dropless held-expert layer for serving. x: (N, D) -> (N, D)."""
    N, D = x.shape
    gates, idx = route(p, x, cfg)
    K = idx.shape[-1]
    key, sizes = _held_pairs(idx, cfg)
    with jax.named_scope("moe.experts"):
        order = jnp.argsort(key.reshape(-1), stable=True)
        tok = order // K
        xs = x[tok]                                     # (N*K, D) by expert
        from repro.kernels.expert_gmm import gmm
        act = activation(cfg.act)
        h = act(gmm(xs, p["wg"], sizes)) * gmm(xs, p["wu"], sizes)
        ys = gmm(h, p["wd"], sizes)
        n = jnp.sum(sizes)
        live = jnp.arange(N * K) < n                   # rows of held pairs
        if "bd_moe" in p:   # CORP hidden-channel compensation, per expert
            e = jnp.minimum(key.reshape(-1)[order], sizes.shape[0] - 1)
            ys = ys + p["bd_moe"].astype(ys.dtype)[e]
        w = gates.reshape(-1)[order]
        part = jnp.where(live[:, None],
                         ys.astype(jnp.float32) * w[:, None], 0.0)
        y = jnp.zeros((N, D), jnp.float32).at[tok].add(part)
    if counts is not None:
        # only the lanes that hold a request count: an idle slot's lane
        # routes its stale token too
        own = jnp.sum(jax.nn.one_hot(key, cfg.held_experts, dtype=jnp.int32)
                      * counts["lanes"].astype(jnp.int32)[:, None, None],
                      (0, 1))
        counts["moe"] = counts.get("moe", 0) + jnp.stack(
            [jnp.sum(own), jnp.sum(own > 0)]).astype(jnp.int32)
    return y.astype(x.dtype)


def apply_moe(p, x, cfg, taps=None, train=False, counts=None):
    """Routed experts plus the shared expert; returns (y, aux_loss).

    Serving (``train=False``, no ``taps``): the dropless held-expert
    layer, aux 0; ``counts`` (a dict holding ``"lanes"``, a (B,) mask of
    the tokens to count) then gets ``"moe"``: int32 (2,), the counted
    tokens' (token, held-expert) pairs and the held experts that got one
    of them. Training and calibration taps: GShard capacity dispatch over
    every expert (the layer must hold them all)."""
    m = cfg.moe
    B, T, D = x.shape
    if not train and taps is None:
        y = _apply_held(p, x.reshape(B * T, D), cfg, counts).reshape(B, T, D)
        if "moe_resid" in p:   # CORP expert-removal compensation
            y = y + jnp.einsum("btd,dc->btc", x.astype(jnp.float32),
                               p["moe_resid"]).astype(y.dtype)
        if "moe_out_b" in p:
            y = y + p["moe_out_b"].astype(y.dtype)
        if "shared" in p:
            y = y + apply_mlp(p["shared"], x, cfg)
        return y, jnp.zeros((), jnp.float32)
    E, K = cfg.eff_num_experts, m.top_k
    if cfg.held_experts != E:
        raise ValueError(f"{cfg.name}: capacity dispatch (training, "
                         f"calibration) needs every routed expert held, "
                         f"not {cfg.held_experts} of {E}")
    xg, n = _group_tokens(x)
    G, tg, _ = xg.shape
    C = max(K, int(np.ceil(tg * K * m.capacity_factor / E)))
    C = min(C, tg)

    gate_vals, gate_idx = route(p, xg, cfg)                 # (G, tg, K)
    probs = jax.nn.softmax(xg.astype(jnp.float32) @ p["router"], axis=-1)

    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (G, tg, K, E)
    flat = onehot.reshape(G, tg * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                    # (G, tg*K, E)
    pos = pos.reshape(G, tg, K, E)
    within_cap = pos < C
    keep = onehot * within_cap                               # (G, tg, K, E)
    # position of each (token, k) inside its *chosen* expert queue: (G, tg, K)
    pos_k = jnp.sum(pos * onehot, axis=-1)
    slot_k = jax.nn.one_hot(pos_k.astype(jnp.int32), C, dtype=jnp.float32)
    # dispatch: (G, tg, E, C) — contraction over K avoids a (K,E,C) blowup
    dispatch = jnp.einsum("gtke,gtkc->gtec", keep, slot_k)
    combine = jnp.einsum("gtke,gtk,gtkc->gtec", keep, gate_vals, slot_k)

    dt = x.dtype
    xe = jnp.einsum("gtec,gtd->gecd", dispatch.astype(dt), xg)  # (G,E,C,D)
    act = activation(cfg.act)
    u = jnp.einsum("gecd,edf->gecf", xe, p["wu"])
    h = act(jnp.einsum("gecd,edf->gecf", xe, p["wg"])) * u
    tap(taps, "moe_h", h)
    if taps is not None:
        taps["moe_mask"] = jnp.einsum("gtec->gec", dispatch).astype(jnp.float32)
    ye = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    if "bd_moe" in p:   # CORP hidden-channel compensation bias (per expert)
        # inside the expert output, before combine: dispatched tokens get
        # it gate-weighted, empty capacity slots are zeroed by combine
        ye = ye + p["bd_moe"].astype(ye.dtype)[None, :, None, :]
    if taps is not None:
        # expert-removal compensation statistics (repro.core.stats._p1_moe):
        # block input x_t plus per-token per-expert *contributions*
        # (gate-weighted expert outputs) — removed experts' contributions
        # are regressed onto x, whose distribution is routing-invariant
        tap(taps, "moe_x", xg)
        tap(taps, "moe_yc",
            jnp.einsum("gtec,gecd->gted", combine.astype(dt), ye))
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(dt), ye)
    if "moe_resid" in p:   # CORP expert-removal compensation (input map)
        y = y + jnp.einsum("gtd,dc->gtc", xg.astype(jnp.float32),
                           p["moe_resid"]).astype(dt)
    if "moe_out_b" in p:   # CORP expert-removal compensation bias
        y = y + p["moe_out_b"].astype(dt)
    y = y.reshape(B, T, D)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg, taps=taps)

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(onehot.sum(2), axis=(0, 1))       # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux
