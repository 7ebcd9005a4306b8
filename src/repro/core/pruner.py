"""CORP pipeline (paper Alg. 1): calibrate -> rank -> compensate -> fold.

``corp_prune(model, params, calib_batches, cfg=PruneConfig(...))`` returns
``(pruned_params, pruned_config, report)``. The pruned model is a physically
smaller standard model (reduced d_ff / per-head qk dims) built by the same
model code — zero inference overhead (paper §1).

Statistics are gathered by the fused ``repro.core.calibrate
.CalibrationEngine``: one jitted, donated-accumulator step per calibration
batch reduces every unit's statistics from a single forward. Under pjit on
a mesh the per-batch reductions compile to psums over the data axes
(DESIGN.md §2.1), and the accumulator pytree can be checkpointed between
batches (``ckpt_dir=`` — fault tolerance for long calibration passes, see
repro.distrib.fault.CalibrationCheckpointer).

``one_traversal=True`` fuses the two calibration passes into one: during
pass 1 the engine speculatively accumulates pass-2 ridge statistics against
top-k candidate keep-sets (sized ``keep_n * (1 + spec_margin)`` from the
first batch's running scores); a final keep-set inside the candidates — the
common case — reconstructs (G, h, t2) exactly with zero extra traversals,
and the rare escape falls back to one targeted mini pass 2. Design, margin
policy, memory bound, and hit-rate study: docs/pipeline.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibrate as calib_mod
from repro.core import ranking as rank_mod
from repro.core import solve as solve_mod
from repro.core import stats as stats_mod
from repro.core.units import Unit, discover_units, get_block, set_block
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class PruneConfig:
    mlp_sparsity: float = 0.5
    attn_sparsity: float = 0.5
    expert_sparsity: float = 0.0  # whole routed experts removed (beyond-paper)
    lam: float = 1e-4            # ridge, relative to mean diagonal
    rank_policy: str = "combined"
    compensate: bool = True      # False = rank-only baseline (paper ablation)
    include_mamba: bool = True   # beyond-paper mamba inner-channel pruning
    round_to: int = 1            # TPU lane alignment (beyond-paper perf mode)
    seed: int = 0


def _keep_count(full: int, sparsity: float, round_to: int) -> int:
    k = int(round(full * (1.0 - sparsity)))
    if round_to > 1:
        k = max(round_to, (k // round_to) * round_to)
    return max(1, min(full, k))


def _attn_keep_n(u: Unit, full: int, pc: PruneConfig) -> int:
    """Kept dims (cls 1) / rotary pairs (cls 2/3) for an attention unit."""
    rt = pc.round_to if u.attn_class == 1 else max(1, pc.round_to // 2)
    return _keep_count(full, pc.attn_sparsity, rt)


_ATTN_KINDS = ("attn", "mla", "cross")


# ---------------------------------------------------------------------------
# statistics accumulation
# ---------------------------------------------------------------------------

def accumulate(step_fn: Callable, params, batches: Iterable) -> Dict:
    """Legacy host-side accumulation loop (one jitted step, tree-add on the
    host per batch). The pipeline itself uses CalibrationEngine's fused
    donated-accumulator step; this stays as the reference implementation
    for parity tests and the loop-vs-fused benchmark
    (benchmarks/bench_calibration.py)."""
    total = None
    jit_step = jax.jit(step_fn)
    for batch in batches:
        total = stats_mod.tree_add(total, jit_step(params, batch))
    assert total is not None, "empty calibration stream"
    return jax.device_get(total)


def _checkpointer(ckpt_dir: Optional[str], tag: str, every: int):
    if ckpt_dir is None:
        return None
    from repro.distrib.fault import CalibrationCheckpointer
    return CalibrationCheckpointer(f"{ckpt_dir}/{tag}", every=every)


# ---------------------------------------------------------------------------
# one-traversal speculative calibration (docs/pipeline.md)
# ---------------------------------------------------------------------------

def _speculative_pass(model, units, params, batches, pc: PruneConfig, *,
                      spec_margin: float, mesh, stats_dtype,
                      ckpt_dir=None, ckpt_every: int = 8):
    """Single traversal gathering pass-1 AND speculative pass-2 statistics.

    The candidate keep-sets are chosen from the *first batch's* ranking
    scores (one extra forward of that batch — not an extra traversal of
    the stream), sized ``keep_n * (1 + spec_margin)`` per unit; the fused
    ``phase="1+2"`` engine then streams the whole set once. Returns
    ``(p1, spec_plan, spec_stats)``.
    """
    import itertools as _it
    it = iter(batches)
    try:
        first = next(it)
    except StopIteration:
        raise ValueError("empty calibration stream") from None
    # the selector only needs the attention logit-energy scores — don't
    # compute (and discard) every dense unit's FxF moments for one batch
    attn_units = [u for u in units if u.kind in _ATTN_KINDS]
    selector = calib_mod.CalibrationEngine(model, attn_units, phase=1,
                                           mesh=mesh,
                                           stats_dtype=stats_dtype)
    s0 = selector.run(params, [first])
    spec_plan = {}
    for u in units:
        if u.kind in _ATTN_KINDS:
            full = s0[u.name]["rank"].shape[-1]
            spec_plan[u.name] = rank_mod.candidate_attn(
                s0[u.name], _attn_keep_n(u, full, pc), spec_margin)
    engine = calib_mod.CalibrationEngine(model, units, phase="1+2",
                                         spec_plan=spec_plan, mesh=mesh,
                                         stats_dtype=stats_dtype)
    combined = engine.run(params, _it.chain([first], it),
                          checkpointer=_checkpointer(ckpt_dir, "pass12",
                                                     ckpt_every))
    return combined["p1"], spec_plan, combined["p2spec"]


def _resolve_attn_pass2(model, units, params, calib_batches, attn_plan,
                        spec_plan, spec_stats, *, mesh, stats_dtype,
                        ckpt_dir=None, ckpt_every: int = 8, say=None):
    """Pass-2 statistics for every unit in ``attn_plan``.

    Speculative mode (``spec_plan`` not None): units whose final keep-set
    fell inside their candidate set reconstruct (G, h, t2) from the
    speculative accumulators — zero additional traversals; units that
    escaped fall back to ONE targeted mini pass 2 reducing only their
    statistics. Two-pass mode (``spec_plan`` None): the classic full
    pass 2. Returns ``(p2, misses)``.
    """
    say = say or (lambda s: None)
    p2, misses = {}, []
    if spec_plan is not None:
        for u in units:
            if u.name not in attn_plan:
                continue
            keep = np.asarray(attn_plan[u.name][0])
            if rank_mod.covers(spec_plan[u.name], keep):
                p2[u.name] = stats_mod.spec_reconstruct(
                    spec_stats[u.name], spec_plan[u.name], keep, u)
            else:
                misses.append(u.name)
        todo = {k: attn_plan[k] for k in misses}
        if todo:
            say(f"pass 2 (targeted): {len(todo)} unit(s) escaped the "
                f"speculative candidates")
    else:
        todo = attn_plan
        if todo:
            say("pass 2: attention compensation statistics")
    if todo:
        engine2 = calib_mod.CalibrationEngine(model, units, phase=2,
                                              plan=todo, mesh=mesh,
                                              stats_dtype=stats_dtype)
        p2.update(engine2.run(params, calib_batches(),
                              checkpointer=_checkpointer(ckpt_dir, "pass2",
                                                         ckpt_every)))
    return p2, misses


# ---------------------------------------------------------------------------
# per-unit folding
# ---------------------------------------------------------------------------

def _gather(a, idx, axis):
    """take_along_axis with idx's leading dims aligned to a's outermost."""
    idx = jnp.asarray(idx)
    shape = [1] * a.ndim
    lead = idx.ndim - 1
    for i in range(lead):
        shape[i] = idx.shape[i]
    shape[axis] = idx.shape[-1]
    return jnp.take_along_axis(a, idx.reshape(shape), axis=axis)


def _fold_mlp_block(p, stats, unit: Unit, pc: PruneConfig, keep, prune,
                    report):
    """Dense MLP (plain/glu) or rwkv channel-mix. keep/prune: (..., n)."""
    w1_keys = [k for k in ("wu", "wg", "wk") if k in p]
    w2_key = "wv" if unit.kind == "rwkv_mlp" else "wd"
    w2 = p[w2_key]                       # (..., F, D)
    new = dict(p)
    keep_j = jnp.asarray(keep)
    prune_j = jnp.asarray(prune)

    def solve_one(mu_sigma, keep, prune, w2):
        mu, sigma = mu_sigma
        lam = pc.lam * jnp.mean(jnp.diagonal(sigma, axis1=-2, axis2=-1))
        sol = solve_mod.ridge_affine(mu, sigma, keep, prune, lam)
        diag = solve_mod.mlp_distortion(sol, w2[prune].astype(jnp.float32))
        return sol["B"], sol["c"], diag

    mu, sigma = jax.vmap(solve_mod.mlp_cov)(stats) if keep_j.ndim > 1 \
        else solve_mod.mlp_cov(stats)
    if keep_j.ndim == 1:
        B, c, diag = solve_one((mu, sigma), keep_j, prune_j, w2)
        w2_S = w2[keep_j]
        w2_P = w2[prune_j]
        comp = jnp.einsum("ps,pd->sd", B, w2_P)
        bias = c @ w2_P
    else:
        flat_ms = (mu.reshape((-1,) + mu.shape[-1:]),
                   sigma.reshape((-1,) + sigma.shape[-2:]))
        kf = keep_j.reshape(-1, keep_j.shape[-1])
        pf = prune_j.reshape(-1, prune_j.shape[-1])
        w2f = w2.reshape((-1,) + w2.shape[-2:])
        B, c, diag = jax.vmap(solve_one)((flat_ms), kf, pf, w2f)
        w2_S = jnp.take_along_axis(w2f, kf[..., None], axis=1)
        w2_P = jnp.take_along_axis(w2f, pf[..., None], axis=1)
        comp = jnp.einsum("rps,rpd->rsd", B, w2_P)
        bias = jnp.einsum("rp,rpd->rd", c, w2_P)
        lead = w2.shape[:-2]
        w2_S = w2_S.reshape(lead + w2_S.shape[-2:])
        comp = comp.reshape(lead + comp.shape[-2:])
        bias = bias.reshape(lead + bias.shape[-1:])
        diag = jax.tree.map(lambda a: a.reshape(lead), diag)

    if pc.compensate:
        new[w2_key] = (w2_S.astype(jnp.float32) + comp).astype(w2.dtype)
        if unit.kind == "rwkv_mlp":
            # fold bias into a dedicated additive term applied before gating
            new["bv_comp"] = bias
        else:
            old_b = p.get("bd", jnp.zeros(bias.shape, jnp.float32))
            new["bd"] = (old_b.astype(jnp.float32) + bias)
    else:
        new[w2_key] = w2_S

    for k1 in w1_keys:
        new[k1] = _gather(p[k1], keep_j, axis=p[k1].ndim - 1)
    for bk in ("bu", "bg"):
        if bk in p:
            new[bk] = _gather(p[bk], keep_j, axis=p[bk].ndim - 1)
    report[unit.name] = jax.device_get(diag)
    return new


def _fold_moe_block(p, stats, unit: Unit, pc: PruneConfig, keep, prune,
                    report):
    """MoE experts: weights (..., E, D, F)/(..., E, F, D); per-expert stats."""
    new = dict(p)
    keep_j = jnp.asarray(keep)           # (..., E, ds)
    prune_j = jnp.asarray(prune)
    w2 = p["wd"]                          # (..., E, F, D)
    lead_shape = w2.shape[:-2]
    w2f = w2.reshape((-1,) + w2.shape[-2:])
    kf = keep_j.reshape(-1, keep_j.shape[-1])
    pf = prune_j.reshape(-1, prune_j.shape[-1])
    muf = np.asarray(stats["s1"], np.float64)
    nf = np.maximum(np.asarray(stats["n"], np.float64), 1.0)[..., None]
    mu = jnp.asarray((muf / nf).reshape(-1, muf.shape[-1]), jnp.float32)
    s2 = np.asarray(stats["s2"], np.float64) / nf[..., None]
    sigma = s2 - (muf / nf)[..., :, None] * (muf / nf)[..., None, :]
    sigma = jnp.asarray(sigma.reshape((-1,) + sigma.shape[-2:]), jnp.float32)

    def solve_one(mu, sigma, keep, prune, w2):
        lam = pc.lam * jnp.mean(jnp.diagonal(sigma, axis1=-2, axis2=-1))
        sol = solve_mod.ridge_affine(mu, sigma, keep, prune, lam)
        diag = solve_mod.mlp_distortion(sol, w2[prune].astype(jnp.float32))
        return sol["B"], sol["c"], diag

    B, c, diag = jax.vmap(solve_one)(mu, sigma, kf, pf, w2f)
    w2_S = jnp.take_along_axis(w2f, kf[..., None], axis=1)
    w2_P = jnp.take_along_axis(w2f, pf[..., None], axis=1)
    if pc.compensate:
        comp = jnp.einsum("rps,rpd->rsd", B, w2_P)
        new["wd"] = (w2_S.astype(jnp.float32) + comp).astype(w2.dtype) \
            .reshape(lead_shape + (kf.shape[-1], w2.shape[-1]))
        new["bd_moe"] = jnp.einsum("rp,rpd->rd", c, w2_P) \
            .reshape(lead_shape + (w2.shape[-1],))
    else:
        new["wd"] = w2_S.reshape(lead_shape + (kf.shape[-1], w2.shape[-1]))
    for k1 in ("wu", "wg"):
        new[k1] = _gather(p[k1], keep_j, axis=p[k1].ndim - 1)
    report[unit.name] = jax.device_get(
        jax.tree.map(lambda a: a.reshape(lead_shape), diag))
    return new


def _fold_moe_experts(p, stats, unit: Unit, pc: PruneConfig, keep, prune,
                      report):
    """Whole-expert removal (beyond-paper MoE extension of Eq. 9).

    The regression vector is the MoE block input concatenated with the
    gate-weighted expert contributions ``z_t = [x_t, c_t1..c_tE]``
    (moments yn/ys1/ys2 from ``repro.core.stats._p1_moe``): removed
    experts' contribution blocks are ridge-regressed onto the *input*
    block. Regressing on x rather than on the retained contributions is
    deliberate — after removal the router renormalizes its gate mass onto
    the surviving experts, shifting the retained-contribution distribution
    away from calibration (a fit against them measurably hurts); the input
    distribution at this block is routing-invariant. The summed solution
    folds into a dense residual map ``moe_resid`` (D, D) and bias
    ``moe_out_b`` applied after combine (repro.models.mlp.apply_moe);
    retained experts' weights are gathered untouched. keep/prune:
    (..., n) expert index arrays. Runs AFTER the hidden-channel fold.
    """
    new = dict(p)
    wd = p["wd"]                          # (..., E, F, D)
    lead = wd.shape[:-3]
    E, F, D = wd.shape[-3:]
    keep_j = jnp.asarray(keep, jnp.int32)
    prune_j = jnp.asarray(prune, jnp.int32)
    nP = prune_j.shape[-1]
    pf = prune_j.reshape(-1, nP)
    R = pf.shape[0]
    V = (E + 1) * D
    yn = jnp.maximum(jnp.asarray(stats["yn"], jnp.float32).reshape(R), 1.0)
    ys1 = jnp.asarray(stats["ys1"], jnp.float32).reshape(R, V)
    ys2 = jnp.asarray(stats["ys2"], jnp.float32).reshape(R, V, V)
    ar = jnp.arange(D, dtype=jnp.int32)
    idx_s = jnp.broadcast_to(ar, (R, D))                   # input block
    idx_p = ((pf + 1)[..., None] * D + ar).reshape(R, nP * D)

    def solve_one(n, s1, s2, i_s, i_p):
        mu = s1 / n
        sigma = s2 / n - jnp.outer(mu, mu)
        lam = pc.lam * jnp.mean(jnp.diagonal(sigma))
        sol = solve_mod.ridge_affine(mu, sigma, i_s, i_p, lam)
        # removed contributions enter the output through identity
        # (y = sum_e c_te) -> w_P is stacked identity blocks
        w_p = jnp.tile(jnp.eye(D, dtype=jnp.float32), (nP, 1))
        diag = solve_mod.mlp_distortion(sol, w_p)
        w = jnp.sum(sol["B"].reshape(nP, D, D), axis=0)    # x -> sum_r c_r
        b0 = jnp.sum(sol["c"].reshape(nP, D), axis=0)
        return w.T, b0, diag                               # y += x @ w.T

    W, b0, diag = jax.vmap(solve_one)(yn, ys1, ys2, idx_s, idx_p)
    if pc.compensate:
        new["moe_resid"] = W.reshape(lead + (D, D))
        new["moe_out_b"] = b0.reshape(lead + (D,))
    new["router"] = _gather(p["router"], keep_j, axis=p["router"].ndim - 1)
    if "router_bias" in p:
        new["router_bias"] = _gather(p["router_bias"], keep_j,
                                     axis=p["router_bias"].ndim - 1)
    for k1 in ("wu", "wg", "wd"):
        new[k1] = _gather(p[k1], keep_j, axis=p[k1].ndim - 3)
    if "bd_moe" in p:
        new["bd_moe"] = _gather(p["bd_moe"], keep_j,
                                axis=p["bd_moe"].ndim - 2)
    report[unit.name + "/experts"] = jax.device_get(
        jax.tree.map(lambda x: x.reshape(lead), diag))
    return new


def _moe_expert_plan(units, p1, cfg, pc: PruneConfig):
    """keep/prune expert index arrays per routed-MoE unit, or {}."""
    if pc.expert_sparsity <= 0 or cfg.moe is None:
        return {}
    keep_n = max(cfg.moe.top_k,
                 _keep_count(cfg.moe.num_experts, pc.expert_sparsity, 1))
    if keep_n >= cfg.moe.num_experts:
        return {}
    return {u.name: rank_mod.rank_experts(p1[u.name], keep_n)
            for u in units if u.kind == "moe"}


def _fold_mamba_block(p, stats, unit: Unit, pc: PruneConfig, keep, prune,
                      report):
    new = dict(p)
    keep_j = jnp.asarray(keep)
    prune_j = jnp.asarray(prune)
    di = p["d_skip"].shape[-1]
    out = p["out_proj"]                   # (..., di, D)

    def solve_one(mu_sigma, keep, prune, w2):
        mu, sigma = mu_sigma
        lam = pc.lam * jnp.mean(jnp.diagonal(sigma, axis1=-2, axis2=-1))
        sol = solve_mod.ridge_affine(mu, sigma, keep, prune, lam)
        diag = solve_mod.mlp_distortion(sol, w2[prune].astype(jnp.float32))
        return sol["B"], sol["c"], diag

    if keep_j.ndim == 1:
        mu, sigma = solve_mod.mlp_cov(stats)
        B, c, diag = solve_one((mu, sigma), keep_j, prune_j, out)
        out_S, out_P = out[keep_j], out[prune_j]
        comp = jnp.einsum("ps,pd->sd", B, out_P)
        bias = c @ out_P
    else:
        mu, sigma = jax.vmap(solve_mod.mlp_cov)(stats)
        B, c, diag = jax.vmap(solve_one)((mu, sigma), keep_j, prune_j, out)
        out_S = jnp.take_along_axis(out, keep_j[..., None], axis=1)
        out_P = jnp.take_along_axis(out, prune_j[..., None], axis=1)
        comp = jnp.einsum("rps,rpd->rsd", B, out_P)
        bias = jnp.einsum("rp,rpd->rd", c, out_P)
    if pc.compensate:
        new["out_proj"] = (out_S.astype(jnp.float32) + comp).astype(out.dtype)
        new["out_b"] = bias
    else:
        new["out_proj"] = out_S

    # gather every channel-wise parameter of the pruned inner dims
    in_proj = p["in_proj"]                 # (..., D, 2di)
    both = jnp.concatenate([keep_j, keep_j + di], axis=-1)
    new["in_proj"] = _gather(in_proj, both, axis=in_proj.ndim - 1)
    new["conv_w"] = _gather(p["conv_w"], keep_j, axis=p["conv_w"].ndim - 1)
    new["conv_b"] = _gather(p["conv_b"], keep_j, axis=p["conv_b"].ndim - 1)
    new["x_proj"] = _gather(p["x_proj"], keep_j, axis=p["x_proj"].ndim - 2)
    new["dt_proj"] = _gather(p["dt_proj"], keep_j, axis=p["dt_proj"].ndim - 1)
    new["dt_bias"] = _gather(p["dt_bias"], keep_j, axis=p["dt_bias"].ndim - 1)
    new["a_log"] = _gather(p["a_log"], keep_j, axis=p["a_log"].ndim - 2)
    new["d_skip"] = _gather(p["d_skip"], keep_j, axis=p["d_skip"].ndim - 1)
    report[unit.name] = jax.device_get(diag)
    return new


def _fold_attn_block(p, p2stats, unit: Unit, cfg, pc: PruneConfig, keep,
                     prune, report):
    """Attention QK fold. keep/prune: dims (class 1) or pairs (class 2/3),
    shape (..., G, n)."""
    new = dict(p)
    cls = unit.attn_class
    keep_j = jnp.asarray(keep)
    prune_j = jnp.asarray(prune)
    mla = unit.kind == "mla"
    qk, kk = ("w_uq_nope", "w_uk_nope") if mla else ("wq", "wk")
    wq, wk = p[qk], p[kk]                 # (..., D, H, dq)
    G = unit.n_groups
    qpg = unit.q_per_group
    dq_full = wq.shape[-1]

    # --- solve per (layer, group), vmapped over flattened leading dims
    lead = keep_j.shape[:-2]

    Gm = jnp.asarray(p2stats["G"])
    hv = jnp.asarray(p2stats["h"])
    t2 = jnp.asarray(p2stats["t2"])

    # flatten (lead..., G) into one vmap dim
    def fl(a, extra):
        return a.reshape((-1,) + a.shape[a.ndim - extra:])
    Gf = fl(Gm, Gm.ndim - len(lead) - 1)
    hf = fl(hv, hv.ndim - len(lead) - 1)
    t2f = t2.reshape(-1)

    if cls == 1:
        def s1(Gm, h, t2):
            lam = pc.lam * jnp.mean(jnp.real(jnp.diag(Gm)))
            sol = solve_mod.solve_full_m(Gm, h, t2, lam)
            if not pc.compensate:
                sol = dict(sol, M=jnp.zeros_like(sol["M"]))
            fq, fk = solve_mod.fold_full_m(sol["M"])
            return fq, fk, {"j_star": sol["j_star"],
                            "j_uncomp": sol["j_uncomp"], "rho2": sol["rho2"]}
        fq, fk, diag = jax.vmap(s1)(Gf, hf, t2f)
        dim_keep, dim_prune = keep_j, prune_j
    else:
        def s2(Gm, h, t2):
            lam = pc.lam * jnp.mean(jnp.real(jnp.diag(Gm)))
            if cls == 2:
                sol = solve_mod.solve_diag_complex(Gm, h, t2, lam)
            else:
                sol = solve_mod.solve_diag_real(Gm, h, t2, lam)
            m = sol["m"] if pc.compensate else jnp.zeros_like(sol["m"])
            if cls == 2:
                bq, bk = solve_mod.fold_diag_complex(m)
            else:
                bq, bk = solve_mod.fold_diag_real(m)
            return bq, bk, {"j_star": sol["j_star"],
                            "j_uncomp": sol["j_uncomp"], "rho2": sol["rho2"]}
        fq, fk, diag = jax.vmap(s2)(Gf, hf, t2f)
        dim_keep = solve_mod.pairs_to_dims(keep_j)
        dim_prune = solve_mod.pairs_to_dims(prune_j)

    def unfl(a):
        return a.reshape(lead + (G,) + a.shape[1:])
    fq, fk = unfl(fq), unfl(fk)
    diag = jax.tree.map(unfl, diag)

    # --- gather kept dims + apply folds
    # wq: (..., D, H, dq) -> (..., D, G, qpg, dq)
    wq_g = wq.reshape(wq.shape[:-2] + (G, qpg, dq_full))
    wk_g = wk.reshape(wk.shape[:-2] + (G, 1, dq_full))
    idx_q = dim_keep[..., None, :, None, :]    # (...,1,G,1,n)
    idx_q = jnp.broadcast_to(
        idx_q, wq_g.shape[:-1] + (dim_keep.shape[-1],))
    wq_S = jnp.take_along_axis(wq_g, idx_q, axis=-1)
    idx_k = jnp.broadcast_to(dim_keep[..., None, :, None, :],
                             wk_g.shape[:-1] + (dim_keep.shape[-1],))
    wk_S = jnp.take_along_axis(wk_g, idx_k, axis=-1)

    if cls == 1:
        wq_new = jnp.einsum("...dgqs,...gst->...dgqt",
                            wq_S.astype(jnp.float32), fq)
        wk_new = jnp.einsum("...dgqs,...gst->...dgqt",
                            wk_S.astype(jnp.float32), fk)
    elif cls == 2:
        # per-pair 2x2 blocks: (..., G, p, 2, 2)
        shp_q = wq_S.shape[:-1] + (dim_keep.shape[-1] // 2, 2)
        wq_pairs = wq_S.reshape(shp_q).astype(jnp.float32)
        wq_new = jnp.einsum("...dgqpi,...gpij->...dgqpj", wq_pairs, fq)
        wq_new = wq_new.reshape(wq_S.shape)
        shp_k = wk_S.shape[:-1] + (dim_keep.shape[-1] // 2, 2)
        wk_pairs = wk_S.reshape(shp_k).astype(jnp.float32)
        wk_new = jnp.einsum("...dgqpi,...gpij->...dgqpj", wk_pairs, fk)
        wk_new = wk_new.reshape(wk_S.shape)
    else:
        # class 3: fold into qk-norm scales (per-head vectors)
        wq_new = wq_S.astype(jnp.float32)
        wk_new = wk_S.astype(jnp.float32)

    new[qk] = wq_new.reshape(wq.shape[:-2] + (G * qpg,
                                              dim_keep.shape[-1])) \
        .astype(wq.dtype)
    new[kk] = wk_new.reshape(wk.shape[:-2] + (G, dim_keep.shape[-1])) \
        .astype(wk.dtype)

    # biases (pre-rope additive -> transformed by the same fold)
    if "bq" in p and not mla:
        bq = p["bq"]                       # (..., H, dq)
        bq_g = bq.reshape(bq.shape[:-2] + (G, qpg, dq_full))
        idx = jnp.broadcast_to(dim_keep[..., :, None, :],
                               bq_g.shape[:-1] + (dim_keep.shape[-1],))
        bq_S = jnp.take_along_axis(bq_g, idx, axis=-1).astype(jnp.float32)
        bk = p["bk"]
        bk_g = bk.reshape(bk.shape[:-2] + (G, 1, dq_full))
        idxk = jnp.broadcast_to(dim_keep[..., :, None, :],
                                bk_g.shape[:-1] + (dim_keep.shape[-1],))
        bk_S = jnp.take_along_axis(bk_g, idxk, axis=-1).astype(jnp.float32)
        if cls == 1:
            bq_S = jnp.einsum("...gqs,...gst->...gqt", bq_S, fq)
            bk_S = jnp.einsum("...gqs,...gst->...gqt", bk_S, fk)
        elif cls == 2:
            sq = bq_S.shape[:-1] + (dim_keep.shape[-1] // 2, 2)
            bq_S = jnp.einsum("...gqpi,...gpij->...gqpj",
                              bq_S.reshape(sq), fq).reshape(bq_S.shape)
            sk = bk_S.shape[:-1] + (dim_keep.shape[-1] // 2, 2)
            bk_S = jnp.einsum("...gqpi,...gpij->...gqpj",
                              bk_S.reshape(sk), fk).reshape(bk_S.shape)
        new["bq"] = bq_S.reshape(bq.shape[:-2]
                                 + (G * qpg, dim_keep.shape[-1]))
        new["bk"] = bk_S.reshape(bk.shape[:-2] + (G, dim_keep.shape[-1]))

    # qk-norm scales: gather kept dims; class 3 folds the scale here
    if "q_scale" in p:
        qs = p["q_scale"]                  # (..., dq) shared across heads
        ks_ = p["k_scale"]
        def expand_scale(s, n_rep):
            # (..., dq) -> (..., n_rep, dq)
            return jnp.broadcast_to(s[..., None, :],
                                    s.shape[:-1] + (n_rep, s.shape[-1]))
        qs_h = expand_scale(qs, G * qpg)
        ks_h = expand_scale(ks_, G)
        qs_g = qs_h.reshape(qs_h.shape[:-2] + (G, qpg, dq_full))
        idx = jnp.broadcast_to(dim_keep[..., :, None, :],
                               qs_g.shape[:-1] + (dim_keep.shape[-1],))
        qs_S = jnp.take_along_axis(qs_g, idx, axis=-1)
        ks_g = ks_h.reshape(ks_h.shape[:-2] + (G, 1, dq_full))
        idxk = jnp.broadcast_to(dim_keep[..., :, None, :],
                                ks_g.shape[:-1] + (dim_keep.shape[-1],))
        ks_S = jnp.take_along_axis(ks_g, idxk, axis=-1)
        if cls == 3:
            # per-pair scale expanded to both dims of the pair
            def pair_expand(v):
                return jnp.repeat(v, 2, axis=-1)
            qs_S = qs_S * pair_expand(fq)[..., :, None, :]
            ks_S = ks_S * pair_expand(fk)[..., :, None, :]
        new["q_scale"] = qs_S.reshape(qs_h.shape[:-2]
                                      + (G * qpg, dim_keep.shape[-1]))
        new["k_scale"] = ks_S.reshape(ks_h.shape[:-2]
                                      + (G, dim_keep.shape[-1]))

    # rope frequency buffers: gather kept pair frequencies per head
    if "rope_inv_q" in p:
        ri_q = p["rope_inv_q"]             # (..., H, dq/2)
        ri_k = p["rope_inv_k"]             # (..., G, dq/2)
        pk = keep_j                        # pair indices (..., G, p)
        riq_g = ri_q.reshape(ri_q.shape[:-2] + (G, qpg, dq_full // 2))
        idx = jnp.broadcast_to(pk[..., :, None, :],
                               riq_g.shape[:-1] + (pk.shape[-1],))
        riq = jnp.take_along_axis(riq_g, idx, axis=-1)
        new["rope_inv_q"] = riq.reshape(ri_q.shape[:-2]
                                        + (G * qpg, pk.shape[-1]))
        idxk = jnp.broadcast_to(pk, ri_k.shape[:-1] + (pk.shape[-1],))
        new["rope_inv_k"] = jnp.take_along_axis(ri_k, idxk, axis=-1)

    report[unit.name] = jax.device_get(diag)
    return new


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def corp_prune(model, params, calib_batches: Callable[[], Iterable],
               pc: PruneConfig = PruneConfig(),
               progress: Optional[Callable[[str], None]] = None,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 8,
               mesh=None, stats_dtype="float32",
               one_traversal: bool = False, spec_margin: float = 0.25):
    """One-shot CORP (Alg. 1): calibrate -> rank -> compensate -> fold.

    Args:
      model: model exposing ``apply(params, batch, taps=...)`` and ``cfg``.
      params: dense parameter pytree (any dtype; statistics are fp32).
      calib_batches: zero-arg callable returning a fresh iterator of
        batches (traversed twice classically: rank pass + attention
        compensation pass; once with ``one_traversal=True`` on the
        speculative hit path).
      pc: sparsities/ridge/ranking-policy knobs, see ``PruneConfig``.
      progress: optional ``fn(str)`` called at each pipeline stage.
      ckpt_dir: when set, each calibration pass checkpoints its statistics
        accumulator every ``ckpt_every`` batches under ``<ckpt_dir>/passN``
        (``pass12`` for the fused one-traversal pass) and resumes from the
        newest valid one (restartable long passes).
      mesh: optional ``jax.sharding.Mesh`` — all calibration passes then
        run mesh-sharded (``CalibrationEngine(mesh=...)``): per-unit
        covariance/Gram blocks column-sharded over the model axis, batch
        contributions psum-reduced, no replicated full Sigma on any device.
        Ranking and folding still happen on host from the gathered sums.
      stats_dtype: activation streaming dtype for all calibration passes
        ("float32" default; "bfloat16" halves calibration HBM traffic,
        accumulators stay fp32 — see ``CalibrationEngine``).
      one_traversal: fuse both passes into a single traversal of the
        calibration set: pass 1 speculatively accumulates pass-2
        cross-moments against top-k candidate keep-sets (sized
        ``keep_n * (1 + spec_margin)`` from the first batch's running
        scores). Attention units whose final keep-set lands inside the
        candidates — the common case, see docs/pipeline.md's hit-rate
        study — solve compensation with zero additional traversals; the
        rest fall back to one targeted mini pass 2.
      spec_margin: candidate safety margin for ``one_traversal`` (0.25
        default — ``keep_n * margin`` extra candidate slots per group;
        memory grows as ``(1+margin)^4`` for class-1 units).

    Returns:
      ``(pruned_params, pruned_config, report)`` — a physically smaller
      standard model (reduced d_ff / per-head qk dims) built by the same
      model code, its config, and per-unit distortion diagnostics + stage
      timings. ``report["traversals"]`` counts calibration-set traversals;
      with ``one_traversal=True``, ``report["speculative"]`` records the
      margin, candidate sizes, and hit/miss units.
    """
    import copy
    cfg = model.cfg
    units = discover_units(cfg)
    say = progress or (lambda s: None)
    report = {"timing": {}, "units": {}}

    calls = [0]
    _orig_batches = calib_batches

    def calib_batches():            # noqa: F811 — counts traversals
        calls[0] += 1
        return _orig_batches()

    speculate = (one_traversal and pc.attn_sparsity > 0
                 and any(u.kind in _ATTN_KINDS for u in units))
    spec_plan = spec_stats = None
    with span("prune.pass1") as stage:
        if speculate:
            say("pass 1+2: one-traversal speculative statistics")
            p1, spec_plan, spec_stats = _speculative_pass(
                model, units, params, calib_batches(), pc,
                spec_margin=spec_margin, mesh=mesh, stats_dtype=stats_dtype,
                ckpt_dir=ckpt_dir, ckpt_every=ckpt_every)
        else:
            say("pass 1: ranking/MLP statistics")
            engine1 = calib_mod.CalibrationEngine(model, units, phase=1,
                                                  mesh=mesh,
                                                  stats_dtype=stats_dtype)
            p1 = engine1.run(params, calib_batches(),
                             checkpointer=_checkpointer(ckpt_dir, "pass1",
                                                        ckpt_every))
    report["timing"]["pass1"] = stage.seconds

    # --- ranking ----------------------------------------------------------
    plan = {}       # unit.name -> (keep, prune) np arrays
    with span("prune.rank") as stage:
        for u in units:
            st = p1[u.name]
            if u.kind in ("mlp", "rwkv_mlp", "moe", "mamba"):
                if u.kind == "mamba" and not pc.include_mamba:
                    continue
                if pc.mlp_sparsity <= 0:
                    continue
                blockp = get_block(params, u)
                if u.shared_expert:
                    blockp = blockp["shared"]
                w2 = blockp["wv" if u.kind == "rwkv_mlp"
                            else "out_proj" if u.kind == "mamba" else "wd"]
                keep_n = _keep_count(u.d_hidden if u.kind != "mamba"
                                     else cfg.mamba.expand * cfg.d_model,
                                     pc.mlp_sparsity, pc.round_to)
                keep, prune = rank_mod.rank_mlp(st, np.asarray(w2), keep_n,
                                                pc.rank_policy)
                plan[u.name] = (keep, prune)
            elif u.kind in _ATTN_KINDS:
                if pc.attn_sparsity <= 0:
                    continue
                full = st["rank"].shape[-1]   # dims (cls1) or pairs (cls2/3)
                keep, prune = rank_mod.rank_attn(st,
                                                 _attn_keep_n(u, full, pc))
                plan[u.name] = (keep, prune)
        e_plan = _moe_expert_plan(units, p1, cfg, pc)
    report["timing"]["rank"] = stage.seconds

    # --- pass 2: attention compensation statistics -------------------------
    attn_plan = {u.name: plan[u.name] for u in units
                 if u.kind in _ATTN_KINDS and u.name in plan}
    p2 = {}
    if attn_plan:
        with span("prune.pass2") as stage:
            p2, misses = _resolve_attn_pass2(
                model, units, params, calib_batches, attn_plan, spec_plan,
                spec_stats, mesh=mesh, stats_dtype=stats_dtype,
                ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, say=say)
        if speculate:
            report["speculative"] = {
                "margin": spec_margin,
                "candidates": {k: int(v.shape[-1])
                               for k, v in spec_plan.items()},
                "hits": sorted(set(attn_plan) - set(misses)),
                "misses": sorted(misses)}
        report["timing"]["pass2"] = stage.seconds

    # --- fold -------------------------------------------------------------
    say("closed-form compensation + fold")
    with span("prune.fold") as stage:
        new_params = copy.deepcopy(jax.device_get(params))
        for u in units:
            if u.name not in plan and u.name not in e_plan:
                continue
            block = get_block(new_params, u)
            if u.name in plan:
                keep, prune = plan[u.name]
                if u.kind in ("mlp", "rwkv_mlp"):
                    tgt = block["shared"] if u.shared_expert else block
                    folded = _fold_mlp_block(tgt, p1[u.name], u, pc, keep,
                                             prune, report["units"])
                    if u.shared_expert:
                        block = dict(block, shared=folded)
                    else:
                        block = folded
                elif u.kind == "moe":
                    block = _fold_moe_block(block, p1[u.name], u, pc, keep,
                                            prune, report["units"])
                elif u.kind == "mamba":
                    block = _fold_mamba_block(block, p1[u.name], u, pc,
                                              keep, prune, report["units"])
                else:
                    block = _fold_attn_block(block, p2[u.name], u, cfg, pc,
                                             keep, prune, report["units"])
            if u.name in e_plan:
                ek, ep = e_plan[u.name]
                block = _fold_moe_experts(block, p1[u.name], u, pc, ek, ep,
                                          report["units"])
            set_block(new_params, u, block)
    report["timing"]["fold"] = stage.seconds
    report["plan_sizes"] = {k: v[0].shape for k, v in plan.items()}
    report["plan_sizes"].update(
        {k + "/experts": v[0].shape for k, v in e_plan.items()})
    report["traversals"] = calls[0]

    new_cfg = cfg.pruned(pc.mlp_sparsity if pc.mlp_sparsity > 0 else 0.0,
                         pc.attn_sparsity if pc.attn_sparsity > 0 else 0.0,
                         round_to=pc.round_to,
                         expert_sparsity=pc.expert_sparsity)
    if not pc.include_mamba and new_cfg.d_inner_kept is not None:
        new_cfg = new_cfg.replace(d_inner_kept=None)
    return new_params, new_cfg, report


def corp_prune_streamed(model, params, calib_batches: Callable[[], Iterable],
                        pc: PruneConfig = PruneConfig(), *,
                        unit_group_size: int = 2,
                        progress: Optional[Callable[[str], None]] = None,
                        mesh=None, stats_dtype="float32",
                        one_traversal: bool = False,
                        spec_margin: float = 0.25):
    """Memory-bounded CORP: identical output to ``corp_prune`` (statistics
    are linear, so partitioning the unit set changes nothing), but only
    ``unit_group_size`` units' statistics are resident at a time.

    At 671B scale the covariance blocks dominate (e.g. one dense-FFN
    Sigma is d_ff^2 fp32 = 1.3 GB at 18432; a full MoE layer's per-expert
    stack is E x d_expert^2 = 4.3 GB) — streaming re-traverses the
    calibration set per group and bounds resident statistics to one group,
    which is how a pruning pass over thousands of layers stays inside host
    memory and can checkpoint between groups (DESIGN.md §2.3).

    Args:
      unit_group_size: units whose statistics are resident concurrently.
      mesh: optional ``jax.sharding.Mesh`` — composes both bounds: the
        active group's statistics are the only ones resident AND they are
        model-sharded across the mesh (``CalibrationEngine(mesh=...)``),
        so per-device residency is group_size x Sigma/m. This is the
        671B-scale configuration from ROADMAP's "Sharded engine" item.
      stats_dtype: activation streaming dtype for every group's passes
        ("float32" default; "bfloat16" halves calibration HBM traffic —
        composes with both bounds above, since it shrinks the *stream*
        while they bound the *resident statistics*).
      one_traversal: speculative pass fusion per unit group — a group with
        attention units traverses the calibration set once instead of
        twice on the speculative hit path (see ``corp_prune``); the
        candidate accumulators obey the same residency bound (they exist
        only for the active group).
      spec_margin: candidate safety margin, as in ``corp_prune``.

    Returns:
      ``(pruned_params, pruned_config, report)`` as ``corp_prune``, with
      ``report['groups']`` counting processed unit groups and
      ``report['traversals']`` total calibration-set traversals.
    """
    import copy
    cfg = model.cfg
    all_units = discover_units(cfg)
    say = progress or (lambda s: None)
    new_params = copy.deepcopy(jax.device_get(params))
    report = {"timing": {}, "units": {}, "groups": 0}
    merged_plan = {}
    spec_report = {"margin": spec_margin, "candidates": {}, "hits": [],
                   "misses": []}

    calls = [0]
    _orig_batches = calib_batches

    def calib_batches():            # noqa: F811 — counts traversals
        calls[0] += 1
        return _orig_batches()

    groups = [all_units[i:i + unit_group_size]
              for i in range(0, len(all_units), unit_group_size)]
    for gi, units in enumerate(groups):
        say(f"group {gi+1}/{len(groups)}: "
            + ", ".join(u.name for u in units))
        speculate = (one_traversal and pc.attn_sparsity > 0
                     and any(u.kind in _ATTN_KINDS for u in units))
        spec_plan = spec_stats = None
        if speculate:
            p1, spec_plan, spec_stats = _speculative_pass(
                model, units, params, calib_batches(), pc,
                spec_margin=spec_margin, mesh=mesh, stats_dtype=stats_dtype)
        else:
            p1 = calib_mod.CalibrationEngine(model, units, phase=1,
                                             mesh=mesh,
                                             stats_dtype=stats_dtype) \
                .run(params, calib_batches())
        plan = {}
        for u in units:
            st = p1[u.name]
            if u.kind in ("mlp", "rwkv_mlp", "moe", "mamba"):
                if (u.kind == "mamba" and not pc.include_mamba) \
                        or pc.mlp_sparsity <= 0:
                    continue
                blockp = get_block(params, u)
                if u.shared_expert:
                    blockp = blockp["shared"]
                w2 = blockp["wv" if u.kind == "rwkv_mlp"
                            else "out_proj" if u.kind == "mamba" else "wd"]
                keep_n = _keep_count(u.d_hidden if u.kind != "mamba"
                                     else cfg.mamba.expand * cfg.d_model,
                                     pc.mlp_sparsity, pc.round_to)
                plan[u.name] = rank_mod.rank_mlp(st, np.asarray(w2), keep_n,
                                                 pc.rank_policy)
            elif u.kind in _ATTN_KINDS and pc.attn_sparsity > 0:
                full = st["rank"].shape[-1]
                plan[u.name] = rank_mod.rank_attn(
                    st, _attn_keep_n(u, full, pc))
        e_plan = _moe_expert_plan(units, p1, cfg, pc)
        attn_plan = {u.name: plan[u.name] for u in units
                     if u.kind in _ATTN_KINDS and u.name in plan}
        p2 = {}
        if attn_plan:
            p2, misses = _resolve_attn_pass2(
                model, units, params, calib_batches, attn_plan, spec_plan,
                spec_stats, mesh=mesh, stats_dtype=stats_dtype, say=say)
            if speculate:
                spec_report["candidates"].update(
                    {k: int(v.shape[-1]) for k, v in spec_plan.items()})
                spec_report["hits"] += sorted(set(attn_plan) - set(misses))
                spec_report["misses"] += sorted(misses)
        for u in units:
            if u.name not in plan and u.name not in e_plan:
                continue
            block = get_block(new_params, u)
            if u.name in plan:
                keep, prune = plan[u.name]
                if u.kind in ("mlp", "rwkv_mlp"):
                    tgt = block["shared"] if u.shared_expert else block
                    folded = _fold_mlp_block(tgt, p1[u.name], u, pc, keep,
                                             prune, report["units"])
                    block = dict(block, shared=folded) if u.shared_expert \
                        else folded
                elif u.kind == "moe":
                    block = _fold_moe_block(block, p1[u.name], u, pc, keep,
                                            prune, report["units"])
                elif u.kind == "mamba":
                    block = _fold_mamba_block(block, p1[u.name], u, pc,
                                              keep, prune, report["units"])
                else:
                    block = _fold_attn_block(block, p2[u.name], u, cfg, pc,
                                             keep, prune, report["units"])
            if u.name in e_plan:
                ek, ep = e_plan[u.name]
                block = _fold_moe_experts(block, p1[u.name], u, pc, ek, ep,
                                          report["units"])
            set_block(new_params, u, block)
        merged_plan.update(plan)
        merged_plan.update({k + "/experts": v for k, v in e_plan.items()})
        report["groups"] += 1

    new_cfg = cfg.pruned(pc.mlp_sparsity if pc.mlp_sparsity > 0 else 0.0,
                         pc.attn_sparsity if pc.attn_sparsity > 0 else 0.0,
                         round_to=pc.round_to,
                         expert_sparsity=pc.expert_sparsity)
    if not pc.include_mamba and new_cfg.d_inner_kept is not None:
        new_cfg = new_cfg.replace(d_inner_kept=None)
    report["plan_sizes"] = {k: v[0].shape for k, v in merged_plan.items()}
    report["traversals"] = calls[0]
    if one_traversal and spec_report["candidates"]:
        report["speculative"] = spec_report
    return new_params, new_cfg, report
