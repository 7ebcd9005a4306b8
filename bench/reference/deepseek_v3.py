"""Plain float32 reference of DeepSeek-V3 at one chip's share of an
expert-parallel deployment, and the seeded weights that both it and the
served program run.

DeepSeek-V3 (arXiv:2412.19437; the published ``config.json`` and its
``modeling_deepseek.py``): pre-RMSNorm blocks; multi-head latent attention
(MLA): q = W_uq · RMSNorm(W_dq x), a per-token latent c = RMSNorm(W_dkv x)
from which each head's no-rope key (W_uk c) and value (W_uv c) are lifted,
and a decoupled rope key W_kr x shared by every head; rope under YaRN
(frequencies blended between kept and interpolated by a ramp over the
original context; the softmax scale 1/sqrt(nope + rope) times
mscale(factor, mscale_all_dim)^2; cos and sin times
mscale(factor, mscale) / mscale(factor, mscale_all_dim)); leading dense
SiLU-gated MLP layers, then MoE layers: sigmoid scores over every routed
expert, a correction bias added only to choose, group-limited choice
(a group scores the sum of its top-2 biased scores; a token chooses in its
top ``topk_group`` groups), top-k, gates the unbiased scores of the chosen
normalised and times ``routed_scale``, plus one shared expert; a final
RMSNorm and an untied head.

At CORP's pruned shapes each head keeps ``qk_kept`` of its no-rope q/k
dims, and the softmax scale stays that of the published widths: CORP folds
its compensation into the weights.

Departures, all shared with the program: the rotary pairs are dims
(2i, 2i+1), as DeepSeek's own inference code pairs them (its Hugging Face
transcription permutes them to halves first: the same rotation); the
multi-token-prediction head is not run (it drafts tokens for speculative
decoding, which this serving does not do); and only the held share of the
routed experts (``held`` from ``first_held``) adds to a token's output, as
on one chip of the deployment, while the router still chooses over all of
them. Every routed expert's part is computed densely for every token and
masked by the routing. Nothing here imports the program; the weights are
laid out under the names the program reads (``seg0/l0`` the dense layer,
``seg1/p0`` the stacked MoE layers).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _key(m: dict) -> str:
    return json.dumps(m, sort_keys=True)


def init(seed: int, m: dict):
    """Weights from ``seed`` for the sizes ``m`` (a configuration's
    ``model`` group), made on the device in one jitted call."""
    return _init(jax.random.key(seed % (2 ** 32)), _key(m))


def _attn(ks, lead, m, dt):
    D, H = m["d_model"], m["n_heads"]
    a = m["mla"]
    Rq, R, r = a["q_lora_rank"], a["kv_lora_rank"], a["qk_rope_dim"]
    dq, dv = m["qk_kept"], a["v_dim"]
    return {
        "w_dq": _normal(next(ks), lead + (D, Rq), D ** -0.5, dt),
        "q_norm_scale": 1.0 + _normal(next(ks), lead + (Rq,), 0.1,
                                      jnp.float32),
        "w_uq_nope": _normal(next(ks), lead + (Rq, H, dq), Rq ** -0.5, dt),
        "w_uq_rope": _normal(next(ks), lead + (Rq, H, r), Rq ** -0.5, dt),
        "w_dkv": _normal(next(ks), lead + (D, R), D ** -0.5, dt),
        "w_k_rope": _normal(next(ks), lead + (D, r), D ** -0.5, dt),
        "kv_norm_scale": 1.0 + _normal(next(ks), lead + (R,), 0.1,
                                       jnp.float32),
        "w_uk_nope": _normal(next(ks), lead + (R, H, dq), R ** -0.5, dt),
        "w_uv": _normal(next(ks), lead + (R, H, dv), R ** -0.5, dt),
        "wo": _normal(next(ks), lead + (H, dv, D), (H * dv) ** -0.5, dt),
    }


def _glu(ks, lead, D, F, dt):
    return {"wg": _normal(next(ks), lead + (D, F), D ** -0.5, dt),
            "wu": _normal(next(ks), lead + (D, F), D ** -0.5, dt),
            "wd": _normal(next(ks), lead + (F, D), F ** -0.5, dt)}


@functools.partial(jax.jit, static_argnums=1)
def _init(key, mkey):
    m = json.loads(mkey)
    dt = jnp.dtype(m["dtype"])
    D, L = m["d_model"], m["n_layers"]
    nd = m["first_k_dense"]
    mo = m["moe"]
    E, F = mo["num_experts"], m["d_ff_kept"]
    Eh = mo.get("held") or E
    V = -(-m["vocab_size"] // 128) * 128
    ks = iter(jax.random.split(key, 64))

    def norm(*shape):
        return 1.0 + _normal(next(ks), shape, 0.1, jnp.float32)

    def block(lead, moe):
        p = {"ln1": {"scale": norm(*lead, D)},
             "ln2": {"scale": norm(*lead, D)},
             "mixer": _attn(ks, lead, m, dt)}
        if not moe:
            p["mlp"] = _glu(ks, lead, D, m["dense_d_ff_kept"], dt)
            return p
        experts = _glu(ks, lead + (Eh,), D, F, dt)
        p["mlp"] = dict(
            experts,
            router=_normal(next(ks), lead + (D, E), D ** -0.5, jnp.float32),
            router_bias=_normal(next(ks), lead + (E,), 0.1, jnp.float32),
            shared=_glu(ks, lead, D, mo["num_shared"] * F, dt))
        return p

    return {"embed": _normal(next(ks), (V, D), 1.0, dt),
            "head": _normal(next(ks), (D, V), D ** -0.5, dt),
            "final_norm": {"scale": norm(D)},
            "seg0": {f"l{j}": block((), False) for j in range(nd)},
            "seg1": {"p0": block((L - nd,), True)}}


def _fp8(w):
    """``w`` rounded to float8 e4m3 with one scale per tensor: the control's
    precision, one step below the configuration's bfloat16."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w)) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn(m: dict):
    """(inverse frequencies (r/2,), cos/sin gain, softmax scale) as
    ``DeepseekV3YarnRotaryEmbedding`` and ``DeepseekV3Attention`` compute
    them; plain rope without a ``rope_scaling`` block."""
    a = m["mla"]
    r, base = a["qk_rope_dim"], m["rope_theta"]
    inv = 1.0 / base ** (np.arange(0, r, 2) / r)
    scale = (a["qk_nope_dim"] + r) ** -0.5
    rs = m.get("rope_scaling")
    if not rs:
        return inv, 1.0, scale

    def get_mscale(s, ms):
        return 1.0 if s <= 1 else 0.1 * ms * math.log(s) + 1.0

    def find_dim(rot):
        return (r * math.log(rs["original_max_position_embeddings"]
                             / (rot * 2 * math.pi))) / (2 * math.log(base))

    lo = max(math.floor(find_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(find_dim(rs["beta_slow"])), r - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(r // 2) - lo) / (hi - lo), 0, 1)
    mask = 1.0 - ramp
    f = rs["factor"]
    inv = inv / f * (1 - mask) + inv * mask
    gain = get_mscale(f, rs["mscale"]) / get_mscale(f, rs["mscale_all_dim"])
    if rs["mscale_all_dim"]:
        scale = scale * get_mscale(f, rs["mscale_all_dim"]) ** 2
    return inv, gain, scale


def _rope(x, pos, inv, gain):
    """x: (T, ..., r) with pairs (2i, 2i+1); inv: (r/2,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[-1:])
    c, s = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def noaux_tc(h, router, bias, mo):
    """DeepSeek-V3's router over every routed expert: (gates (T, k),
    experts (T, k))."""
    T, E = h.shape[0], router.shape[-1]
    scores = jax.nn.sigmoid(jnp.dot(h, router, precision=HIGHEST))
    choice = scores + bias
    G = mo["n_group"]
    group = jax.lax.top_k(choice.reshape(T, G, E // G), 2)[0].sum(-1)
    _, gi = jax.lax.top_k(group, mo["topk_group"])
    gmask = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], gi].set(True)
    choice = jnp.where(jnp.repeat(gmask, E // G, axis=1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, mo["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if mo["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * mo["routed_scale"], idx


def logits_rows(w, tokens, rows, mkey, fp8=False):
    """Logits (R, vocab) at positions ``rows`` of the causal forward over
    ``tokens`` (T,), in float32 at the highest matmul precision. ``fp8``
    rounds every weight matrix to float8 first: the control."""
    return _rows(w, tokens, rows, mkey, fp8)[0]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _rows(w, tokens, rows, mkey, fp8=False):
    """(logits at ``rows``, and at each of them the number of MoE layers
    whose top-k choice changes when the router's input is rounded to
    bfloat16, as the program's is)."""
    m = json.loads(mkey)
    f32 = jnp.float32
    q_of = _fp8 if fp8 else (lambda a: a.astype(f32))
    eps, H = m["norm_eps"], m["n_heads"]
    mo = m["moe"]
    inv, gain, scale = yarn(m)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = q_of(w["embed"])[tokens]
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    BQ = min(256, T)                  # query rows per attention block

    def glu(h, p):
        g = jax.nn.silu(dot("td,df->tf", h, q_of(p["wg"])))
        return dot("tf,fd->td", g * dot("td,df->tf", h, q_of(p["wu"])),
                   q_of(p["wd"]))

    def attention(x, a):
        cq = _rms(dot("td,dr->tr", x, q_of(a["w_dq"])), a["q_norm_scale"],
                  eps)
        q_nope = dot("tr,rhq->thq", cq, q_of(a["w_uq_nope"]))
        q_rope = _rope(dot("tr,rhq->thq", cq, q_of(a["w_uq_rope"])), pos,
                       inv, gain)
        c = _rms(dot("td,dr->tr", x, q_of(a["w_dkv"])), a["kv_norm_scale"],
                 eps)
        k_rope = _rope(dot("td,dq->tq", x, q_of(a["w_k_rope"])), pos, inv,
                       gain)
        k_nope = dot("tr,rhq->thq", c, q_of(a["w_uk_nope"]))
        v = dot("tr,rhv->thv", c, q_of(a["w_uv"]))

        def block(q0):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, BQ, 0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, BQ, 0)
            s = (dot("thq,shq->hts", qn, k_nope)
                 + dot("thq,sq->hts", qr, k_rope)) * scale
            keep = (q0 + jnp.arange(BQ))[:, None] >= pos[None, :]
            s = jnp.where(keep[None], s, -jnp.inf)
            return dot("hts,shv->thv", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, jnp.arange(0, T, BQ)).reshape(T, H, -1)
        return dot("thv,hvd->td", o, q_of(a["wo"]))

    def moe(h, p):
        gates, idx = noaux_tc(h, q_of(p["router"]), p["router_bias"], mo)
        _, near = noaux_tc(h.astype(jnp.bfloat16).astype(f32),
                           q_of(p["router"]), p["router_bias"], mo)
        flip = jnp.any(jnp.sort(idx, -1) != jnp.sort(near, -1), -1)
        first = mo.get("first_held", 0)
        held = jnp.arange(first, first + p["wg"].shape[0])
        wt = jnp.sum(gates[:, :, None] * (idx[:, :, None] == held), 1)
        g = jax.nn.silu(dot("td,edf->tef", h, q_of(p["wg"])))
        u = dot("td,edf->tef", h, q_of(p["wu"]))
        y = dot("tef,efd->ted", g * u, q_of(p["wd"]))
        return dot("te,ted->td", wt, y) + glu(h, p["shared"]), flip

    def layer(x, p, is_moe):
        x = x + attention(_rms(x, p["ln1"]["scale"], eps), p["mixer"])
        h = _rms(x, p["ln2"]["scale"], eps)
        if not is_moe:
            return x + glu(h, p["mlp"])
        y, flip = moe(h, p["mlp"])
        return x + y, flip

    for j in range(m["first_k_dense"]):
        x = layer(x, w["seg0"][f"l{j}"], False)
    x, flips = jax.lax.scan(lambda x, p: layer(x, p, True), x,
                            w["seg1"]["p0"])
    x = _rms(x[rows], w["final_norm"]["scale"], eps)
    return (dot("rd,dv->rv", x, q_of(w["head"])[:, :m["vocab_size"]]),
            jnp.sum(flips, 0)[rows])


def gaps(w, m: dict, prompt, served, fp8=False):
    """Per served position, how far below the reference's best logit the
    chosen token's logit lies: the served tokens', or with ``fp8`` the
    tokens the float8 control puts first. Returns a float64 array (G,)."""
    return readings(w, m, prompt, served, fp8)[0]


def readings(w, m: dict, prompt, served, fp8=False):
    """(``gaps``, and per served position the number of MoE layers whose
    routing a bfloat16 rounding of the router's input changes there: the
    near-ties that the program's bfloat16 can tip), from one forward."""
    key = _key(m)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    T = len(seq)
    Tp = -(-T // 1024) * 1024         # a few padded lengths, few compiles
    toks = np.zeros((Tp,), np.int32)
    toks[:T] = seq
    rows = np.arange(len(prompt) - 1, T, dtype=np.int32)
    ref, flips = _rows(w, jnp.asarray(toks), jnp.asarray(rows), key)
    ref = np.asarray(ref, np.float64)
    if fp8:
        low = np.asarray(logits_rows(w, jnp.asarray(toks), jnp.asarray(rows),
                                     key, True))
        chosen = np.argmax(low, -1)
    else:
        chosen = np.asarray(served)
    return ref.max(-1) - ref[np.arange(len(rows)), chosen], np.asarray(flips)
