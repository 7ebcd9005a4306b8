"""DeepSeek-V3 at one chip's share of expert-parallel serving, at toy size
on the CPU in float32: the served program against the plain reference
(``bench/reference/deepseek_v3.py``), the published router, the held
experts' shares, dropless routing, YaRN, and the two kernels it adds
(``flash_decode``'s latent shape, the experts' grouped matmul)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models.common import yarn_gains, yarn_inv_freq

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def toy(held=4, first_held=2):
    """reduced() DeepSeek-V3 at CORP's pruned shapes, float32, holding
    ``held`` of its 8 routed experts (4 groups of 2, top-2 in 2 groups)."""
    cfg = reduced(get_config("deepseek-v3-671b")).replace(
        dtype="float32", qk_kept=8, d_ff_kept=64, dense_d_ff_kept=128)
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, held=held, first_held=first_held))


def as_group(cfg) -> dict:
    """A benchmark configuration's ``model`` group for ``cfg``."""
    m = dataclasses.asdict(cfg)
    return json.loads(json.dumps(m))


def test_model_group_round_trips():
    """ModelConfig(**group) turns the JSON groups back into dataclasses."""
    cfg = toy()
    again = ModelConfig(**as_group(cfg))
    assert again == cfg and again.moe.held == 4 and \
        again.rope_scaling.factor == 40


def test_served_logits_match_reference():
    """The tokens the ServeEngine serves (bucketed prefill, then decode
    through its slot cache) are the reference's best, and the model's
    ragged prefill and decode steps give the reference's logits at every
    served position. Tolerance 1e-4: float32 on
    both sides, differing only in summation order and in the absorbed
    decode (q_nope W_uk against the latent), each ~1e-6 of logits of
    order 1; a routing flip at a near-tie would show far above it."""
    from repro.serve import Request, ServeEngine
    ref = _reference()
    cfg = toy()
    m = as_group(cfg)
    w = ref.init(5, m)
    model = build_model(ModelConfig(**m))
    eng = ServeEngine(model, w, n_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 12)]
    out = eng.run([Request(rid=i, tokens=p, gen=9)
                   for i, p in enumerate(prompts)])
    moe_layers = cfg.n_layers - cfg.first_k_dense
    for p, c in zip(prompts, out):
        g = ref.gaps(w, m, p, c.tokens)
        assert float(np.max(g)) < 1e-4, g
        # the same forward counts the routing near-ties at each position
        again, ties = ref.readings(w, m, p, c.tokens)
        np.testing.assert_array_equal(again, g)
        assert ties.shape == g.shape
        assert ((0 <= ties) & (ties <= moe_layers)).all(), ties
    # logits themselves, position by position, from the same steps
    p = prompts[1]
    seq = np.concatenate([p, out[1].tokens[:-1]])
    toks = np.zeros((1, 16), np.int32)
    toks[0, :len(p)] = p
    got = []
    logits, cache = model.prefill(eng.params, {"tokens": jnp.asarray(toks)},
                                  32, lengths=jnp.asarray([len(p)]))
    got.append(np.asarray(logits[0, -1, :cfg.vocab_size]))
    for t in out[1].tokens[:-1]:
        logits, cache, _ = model.decode_step(
            eng.params, jnp.full((1, 1), t, jnp.int32), cache,
            count_lanes=jnp.ones((1,), bool))
        got.append(np.asarray(logits[0, -1, :cfg.vocab_size]))
    want = np.asarray(ref.logits_rows(
        w, jnp.asarray(np.pad(seq, (0, 32 - len(seq)))),
        jnp.arange(len(p) - 1, len(seq)), ref._key(m)))
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4, rtol=1e-4)


def noaux_tc_literal(logits, bias, n_group, topk_group, top_k, scale):
    """The published router (modeling_deepseek.py MoEGate, noaux_tc),
    transcribed line by line in numpy."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    n = scores.shape[0]
    scores_for_choice = scores + bias[None]
    group_scores = np.sort(scores_for_choice.reshape(n, n_group, -1),
                           -1)[..., -2:].sum(-1)
    group_idx = np.argsort(-group_scores, -1, kind="stable")[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, -1)
    score_mask = np.repeat(group_mask, scores.shape[1] // n_group, -1)
    tmp_scores = np.where(score_mask > 0, scores_for_choice, -np.inf)
    topk_idx = np.argsort(-tmp_scores, -1, kind="stable")[:, :top_k]
    topk_weight = np.take_along_axis(scores, topk_idx, -1)
    topk_weight = topk_weight / (topk_weight.sum(-1, keepdims=True) + 1e-20)
    return topk_weight * scale, topk_idx


@pytest.mark.parametrize("E,G,tg,k", [(256, 8, 4, 8), (8, 4, 2, 2)])
def test_router_matches_published_noaux_tc(E, G, tg, k):
    cfg = get_config("deepseek-v3-671b")
    cfg = cfg.replace(d_model=32, moe=dataclasses.replace(
        cfg.moe, num_experts=E, n_group=G, topk_group=tg, top_k=k))
    key = jax.random.split(jax.random.PRNGKey(E), 3)
    p = {"router": jax.random.normal(key[0], (32, E)) * 32 ** -0.5,
         "router_bias": jax.random.normal(key[1], (E,)) * 0.1}
    x = jax.random.normal(key[2], (64, 32))
    gates, idx = mlp_mod.route(p, x, cfg)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    wg, wi = noaux_tc_literal(logits, np.asarray(p["router_bias"]), G, tg,
                              k, 2.5)
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx),
                                                     order, -1),
                                  np.sort(wi, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(wg, np.argsort(wi, -1), -1), rtol=1e-5)
    # group-limited: every token's experts lie in at most tg groups
    assert max(len(set(r // (E // G))) for r in np.asarray(idx)) <= tg


def _moe_params(cfg, seed=0):
    ref = _reference()
    w = ref.init(seed, as_group(cfg))
    return jax.tree.map(lambda a: a[0], w["seg1"]["p0"]["mlp"])


def test_held_shares_add_up_to_the_uncut_layer():
    """Each chip of a 4-way expert-parallel layer holds 2 of the 8 routed
    experts; the routed parts of the four shares, with the shared expert
    counted once, add up to the layer that holds them all."""
    full = toy(held=None, first_held=0)
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, full.d_model))
    whole, _ = mlp_mod.apply_moe(p, x, full)
    routed = {k: v for k, v in p.items() if k != "shared"}
    parts = []
    for first in range(0, 8, 2):
        cfg = toy(held=2, first_held=first)
        share = dict(routed, **{k: p[k][first:first + 2]
                                for k in ("wg", "wu", "wd")})
        parts.append(mlp_mod.apply_moe(share, x, cfg)[0])
    shared = mlp_mod.apply_mlp(p["shared"], x, full)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)


def test_dropless_output_does_not_depend_on_the_batch():
    """A token's output is the same alone and among 47 others routed to
    the same experts (the capacity dispatch would drop some of them)."""
    cfg = toy(held=None, first_held=0)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 48, cfg.d_model))
    together, _ = mlp_mod.apply_moe(p, x, cfg)
    alone = [mlp_mod.apply_moe(p, x[:, i:i + 1], cfg)[0]
             for i in (0, 17, 47)]
    for i, a in zip((0, 17, 47), alone):
        np.testing.assert_allclose(np.asarray(a[0, 0]),
                                   np.asarray(together[0, i]), atol=1e-6)


def test_engine_counts_held_pairs():
    """The decode step hands back the (token, held expert) pairs and the
    held experts hit of the lanes that hold a request: holding every
    expert, each such lane gives top_k pairs per MoE layer per step, and
    an idle slot's lane none."""
    from repro.serve import ServeEngine, synthetic_trace
    cfg = toy(held=None, first_held=0)
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), n_slots=3,
                      max_len=32)
    eng.run(synthetic_trace(4, cfg.vocab_size, seed=2, prompt_range=(4, 8),
                            gen_range=(3, 6)))
    moe_layers = cfg.n_layers - cfg.first_k_dense
    st = eng.stats
    assert st["decode_lanes"] < st["decode_steps"] * 3     # idle lanes ran
    assert st["moe_pairs"] == st["decode_lanes"] * cfg.moe.top_k \
        * moe_layers
    assert 0 < st["moe_experts_hit"] <= st["decode_steps"] * 8 * moe_layers


def yarn_literal(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV3YarnRotaryEmbedding's inv_freq, transcribed."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_matches_the_published_formula():
    cfg = get_config("deepseek-v3-671b")
    rs = cfg.rope_scaling
    got = yarn_inv_freq(64, cfg.rope_theta, rs)
    np.testing.assert_allclose(got, yarn_literal(64, 1e4, 40, 4096, 32, 1),
                               rtol=1e-12)
    gain, soft = yarn_gains(rs)
    assert gain == 1.0
    assert soft == pytest.approx((0.1 * math.log(40) + 1) ** 2) == \
        pytest.approx(1.8739, abs=1e-4)
    assert attn_mod.mla_scale(cfg) == pytest.approx(soft / math.sqrt(192))
    # CORP keeps the published scale
    assert attn_mod.mla_scale(cfg.replace(qk_kept=64)) == \
        attn_mod.mla_scale(cfg)
    # dims 0-9 keep their frequency, 23-31 are interpolated by 40
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], base[:10])
    np.testing.assert_allclose(got[23:], base[23:] / 40)


@pytest.mark.parametrize("S,bs,last", [(64, 16, (0, 17, 63)),
                                       (128, 64, (5, 64, 127))])
def test_latent_decode_kernel_matches_ref(S, bs, last):
    """The latent kernel (interpret mode) reads a stacked latent cache at
    a layer, each row as the key and its first dv lanes as the value."""
    from repro.kernels.flash_decode import ops as dops
    k = jax.random.split(jax.random.PRNGKey(S), 2)
    B, H, C, dv = len(last), 8, 24, 16
    q = jax.random.normal(k[0], (B, H, C))
    lat = jax.random.normal(k[1], (3, B, S, C))
    last = jnp.asarray(last, jnp.int32)
    got = dops.decode_latent(q, lat, last, layer=jnp.int32(2), scale=0.3,
                             dv=dv, bs=bs, impl="interpret")
    want = dops.decode_latent(q, lat, last, layer=jnp.int32(2), scale=0.3,
                              dv=dv, bs=bs, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("sizes", [(5, 0, 17, 6), (0, 0, 0, 0), (3, 3, 3, 3)])
def test_expert_gmm_matches_ref(sizes):
    """megablox's grouped matmul (interpret) against ragged_dot on the rows
    the groups cover."""
    from repro.kernels.expert_gmm import ops as gops
    k = jax.random.split(jax.random.PRNGKey(sum(sizes)), 2)
    lhs = jax.random.normal(k[0], (40, 32))
    rhs = jax.random.normal(k[1], (4, 32, 64))
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    got = gops.gmm(lhs, rhs, gs, impl="interpret")
    want = gops.gmm(lhs, rhs, gs, impl="ref")
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=1e-4, rtol=1e-4)
