"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs
pure-jnp oracle, plus hypothesis property tests (assignment (c))."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.kernels.flash_attention import ops as fops, ref as fref
from repro.kernels.gram import ops as gops, ref as gref
from repro.kernels.wkv6 import ops as wops, ref as wref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,s,h,hkv,dq,dv", [
    (1, 128, 128, 4, 4, 32, 32),      # MHA square
    (2, 128, 256, 4, 2, 64, 64),      # GQA, longer kv
    (1, 256, 256, 8, 1, 16, 32),      # MQA, dv != dq
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_vs_ref(b, t, s, h, hkv, dq, dv, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, dq), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dq), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dv), dtype)
    ref = fref.attention(q, k, v, causal=causal, window=window)
    pal = fops.attention(q, k, v, causal=causal, window=window,
                         impl="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)
    xla = fops.attention(q, k, v, causal=causal, window=window, impl="xla",
                         bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(xla, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,s,h,hkv,dq,dv,blocks", [
    (2, 197, 197, 12, 12, 32, 64, "whole_sequence"),  # DeiT-Base, CORP qk 32
    (2, 197, 197, 12, 12, 64, 64, "whole_sequence"),  # DeiT-Base, dense
    (3, 197, 197, 12, 12, 64, 64, "whole_sequence"),  # odd batch, 3 programs
    (2, 100, 120, 8, 2, 32, 64, "whole_sequence"),    # GQA, cross lengths
    (2, 64, 80, 4, 4, 48, 96, "whole_sequence"),      # heads straddle lanes
    (2, 16, 24, 2, 2, 32, 32, "whole_sequence"),      # under 128 lanes
    (1, 512, 2048, 2, 2, 64, 64, "tiled"),            # over the VMEM budget
])
def test_flash_bidirectional_vs_ref(b, t, s, h, hkv, dq, dv, blocks, dtype):
    """Bidirectional attention through the Pallas kernel: a sequence that
    fits VMEM whole takes the whole-sequence path, a longer one the tiled
    path; both match the oracle."""
    from repro import kernels
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, dq), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dq), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dv), dtype)
    before = kernels.RESOLVED.copy()
    pal = fops.attention(q, k, v, causal=False, impl="interpret")
    assert kernels.RESOLVED - before == {("flash_attention", "interpret"): 1,
                                         ("attention_blocks", blocks): 1}
    ref = fref.attention(q, k, v, causal=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def _attention_blocks(q, k, v, **mask):
    """The ``attention_blocks`` path one Pallas call traces to."""
    from repro import kernels
    before = kernels.RESOLVED.copy()
    jax.eval_shape(lambda q, k, v: fops.attention(q, k, v, impl="pallas",
                                                  **mask), q, k, v)
    took = kernels.RESOLVED - before
    (path,) = [p for op, p in took if op == "attention_blocks"]
    return path


def test_attention_blocks_follow_the_shape():
    """Masking and lengths pick the path: causal and windowed prefills
    (qwen2 chat, DeepSeek-V3 MLA) stay tiled; DeiT's bidirectional 197
    tokens run whole, two images a program at the classify batch."""
    from repro.kernels.flash_attention.flash_attention import (
        whole_sequence_block)
    sd = jax.ShapeDtypeStruct
    qwen = (sd((1, 512, 12, 64), jnp.bfloat16),
            sd((1, 512, 2, 64), jnp.bfloat16),
            sd((1, 512, 2, 128), jnp.bfloat16))
    assert _attention_blocks(*qwen, causal=True) == "tiled"
    assert _attention_blocks(*qwen, causal=True, window=256) == "tiled"
    mla = (sd((1, 256, 128, 192), jnp.bfloat16),) * 2 + (
        sd((1, 256, 128, 128), jnp.bfloat16),)
    assert _attention_blocks(*mla, causal=True) == "tiled"
    deit = (sd((256, 197, 12, 32), jnp.float32),) * 2 + (
        sd((256, 197, 12, 64), jnp.float32),)
    assert _attention_blocks(*deit, causal=False) == "whole_sequence"
    assert _attention_blocks(*deit, causal=True) == "tiled"
    assert whole_sequence_block(*(x.shape for x in deit), jnp.float32,
                                causal=False, window=None) == 2
    long = (sd((1, 4096, 8, 64), jnp.float32),) * 3
    assert _attention_blocks(*long, causal=False) == "tiled"


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs, kernel bodies
    excluded."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_whole_sequence_is_one_kernel_on_q_k_v_in_place():
    """A DeiT-shaped call is one ``pallas_call`` named for the benchmark's
    ``flash_attention`` pattern, fed q/k/v by reshapes alone: no pad or
    transpose of them is left."""
    q = jnp.zeros((4, 197, 12, 32))
    v = jnp.zeros((4, 197, 12, 64))
    jaxpr = jax.make_jaxpr(lambda q, k, v: fops.attention(
        q, k, v, causal=False, impl="pallas"))(q, q, v).jaxpr
    eqns = list(_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert "flash_attention" in calls[0].params["name"]
    names = {e.primitive.name for e in eqns}
    assert not names & {"pad", "transpose", "concatenate"}, names


def test_flash_xla_grad_matches_ref_grad():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 16))
    k = jax.random.normal(ks[1], (1, 128, 2, 16))
    v = jax.random.normal(ks[2], (1, 128, 2, 16))

    def loss(f):
        def inner(q, k, v):
            return jnp.sum(jnp.square(f(q, k, v)))
        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    g_ref = loss(lambda q, k, v: fref.attention(q, k, v, causal=True))
    g_xla = loss(lambda q, k, v: fops.attention(q, k, v, causal=True,
                                                impl="xla", bq=32, bk=32))
    for a, b in zip(g_ref, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(t=st.sampled_from([64, 128]), h=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 100))
def test_flash_softmax_rows_property(t, h, seed):
    """Attention output must lie in the convex hull of V rows: with V = const
    vector c, output == c exactly (softmax rows sum to 1)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(k1, (1, t, h, 16))
    k = jax.random.normal(k2, (1, t, h, 16))
    v = jnp.ones((1, t, h, 16)) * 3.5
    out = fops.attention(q, k, v, causal=True, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-4)


# ---------------------------------------------------------------------------
# gram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,f", [(128, 128), (512, 256), (1024, 128)])
def test_gram_vs_ref(n, f, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, f), dtype)
    a = gops.gram(x, impl="interpret")
    b = gref.gram(x)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(a["s2"]), np.asarray(b["s2"]),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(a["s1"]), np.asarray(b["s1"]),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,f,bf,bn", [
    (300, 100, 32, 128),      # neither dim divisible -> both axes padded
    (100, 300, 128, 64),      # token dim padded, feature dim padded
    (257, 129, 128, 512),     # bn clamps to N, F one over the block
    (500, 64, 32, 256),       # only token-dim padding
])
def test_gram_padding_non_divisible(n, f, bf, bn):
    """Zero-padding lifts the old F%bf==0 / N%bn==0 assertion: arbitrary
    DeiT/LM shapes must match the reference exactly (zero rows/cols are
    invisible to both linear reductions)."""
    x = jax.random.normal(jax.random.PRNGKey(42), (n, f))
    a = gops.gram(x, impl="interpret", bf=bf, bn=bn)
    b = gref.gram(x)
    assert a["s2"].shape == (f, f) and a["s1"].shape == (f,)
    np.testing.assert_allclose(np.asarray(a["s2"]), np.asarray(b["s2"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a["s1"]), np.asarray(b["s1"]),
                               rtol=1e-4, atol=1e-4)


def test_gram_ops_default_dispatch_cpu():
    """On CPU the resolver picks the jnp reference (Pallas stays off the
    production path) and odd shapes go through without assertion."""
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 23))
    out = gops.gram(x)
    ref = gref.gram(x)
    np.testing.assert_allclose(np.asarray(out["s2"]), np.asarray(ref["s2"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,fx,fy,bf,bn", [
    (256, 128, 64, 128, 128),     # divisible shard tile
    (300, 100, 48, 32, 128),      # all dims padded, local tile only
    (128, 96, 96, 128, 512),      # square cross == gram
])
def test_gram_cross_vs_ref(n, fx, fy, bf, bn):
    """Rectangular X^T Y slab (the per-shard gram) matches the reference —
    zero-padding applies to each input's local shape independently."""
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (n, fx))
    y = jax.random.normal(ky, (n, fy))
    a = gops.gram_cross(x, y, impl="interpret", bf=bf, bn=bn)
    b = gref.gram_cross(x, y)
    assert a["s2"].shape == (fx, fy) and a["s1"].shape == (fy,)
    np.testing.assert_allclose(np.asarray(a["s2"]), np.asarray(b["s2"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a["s1"]), np.asarray(b["s1"]),
                               rtol=1e-4, atol=1e-4)


def test_gram_cross_column_blocks_tile_full_gram():
    """Concatenating every shard's gram_cross slab over the column axis must
    reproduce gram(x) exactly — the invariant the model-sharded calibration
    layout rests on (docs/calibration.md)."""
    x = jax.random.normal(jax.random.PRNGKey(11), (192, 64))
    full = gref.gram(x)
    m = 4
    fl = x.shape[1] // m
    slabs = [gops.gram_cross(x, x[:, j * fl:(j + 1) * fl], impl="ref")
             for j in range(m)]
    s2 = np.concatenate([np.asarray(s["s2"]) for s in slabs], axis=1)
    s1 = np.concatenate([np.asarray(s["s1"]) for s in slabs])
    np.testing.assert_allclose(s2, np.asarray(full["s2"]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s1, np.asarray(full["s1"]), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_gram_psd_property(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (256, 128))
    s2 = gops.gram(x, impl="interpret")["s2"]
    evs = np.linalg.eigvalsh(np.asarray(s2))
    assert evs.min() > -1e-3
    # symmetry
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s2).T, atol=1e-4)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,h,n,chunk", [(64, 2, 16, 16), (128, 1, 32, 32),
                                         (256, 4, 8, 64)])
def test_wkv6_vs_ref(t, h, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B = 2
    r = jax.random.normal(ks[0], (B, t, h, n))
    k = jax.random.normal(ks[1], (B, t, h, n))
    v = jax.random.normal(ks[2], (B, t, h, n))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, t, h, n))) * 0.6 + 0.35
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    y_ref, s_ref = wref.wkv6(r, k, v, w, u)
    y_pal, s_pal = wops.wkv6(r, k, v, w, u, impl="interpret", chunk=chunk)
    y_xla, s_xla = wops.wkv6(r, k, v, w, u, impl="xla", chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)


def test_wkv6_state_continuation():
    """Running two halves with carried state == one full pass."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, T, H, N = 1, 64, 2, 16
    r = jax.random.normal(ks[0], (B, T, H, N))
    k = jax.random.normal(ks[1], (B, T, H, N))
    v = jax.random.normal(ks[2], (B, T, H, N))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    y_full, s_full = wref.wkv6(r, k, v, w, u)
    y1, s1 = wref.wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u)
    y2, s2 = wref.wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u,
                       state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 500))
def test_wkv6_decay_zero_kills_history(seed):
    """w == tiny -> state holds only the previous step's kv outer product
    (decay applies to S BEFORE the new kv is added), so
    y_t = (r_t . k_{t-1}) v_{t-1} + (r_t . (u*k_t)) v_t."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, T, H, N = 1, 16, 1, 8
    r = jax.random.normal(ks[0], (B, T, H, N))
    k = jax.random.normal(ks[1], (B, T, H, N))
    v = jax.random.normal(ks[2], (B, T, H, N))
    u = jax.random.normal(ks[3], (H, N)) * 0.1
    w = jnp.full((B, T, H, N), 1e-30)
    y, _ = wref.wkv6(r, k, v, w, u)
    bonus = jnp.einsum("bthn,hn,bthn->bth", r, u, k)[..., None] * v
    kprev = jnp.concatenate([jnp.zeros_like(k[:, :1]), k[:, :-1]], 1)
    vprev = jnp.concatenate([jnp.zeros_like(v[:, :1]), v[:, :-1]], 1)
    hist = jnp.einsum("bthn,bthn->bth", r, kprev)[..., None] * vprev
    np.testing.assert_allclose(np.asarray(y), np.asarray(bonus + hist),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# flash_decode (split-KV decode attention)
# ---------------------------------------------------------------------------

from repro.kernels.flash_decode import ops as dops, ref as dref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hkv,dq,dv,bs", [
    (2, 256, 4, 4, 32, 32, 64),       # MHA
    (1, 512, 8, 2, 64, 64, 128),      # GQA
    (2, 256, 4, 1, 16, 32, 64),       # MQA, dv != dq
])
def test_flash_decode_vs_ref(b, s, h, hkv, dq, dv, bs, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, dq), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dq), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dv), dtype)
    # ragged validity (different live lengths per row, like a real cache)
    lens = jnp.asarray([s // 2, s][:b] + [s] * max(0, b - 2))
    valid = jnp.arange(s)[None, :] < lens[:, None]
    ref = dref.decode_attention(q, k, v, valid, scale=0.125)
    pal = dops.decode_attention(q, k, v, valid, scale=0.125, bs=bs,
                                impl="interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 300), nsplit=st.sampled_from([2, 4, 8]))
def test_flash_decode_split_invariance(seed, nsplit):
    """The logsumexp merge must make the result independent of the split
    count (the FlashDecoding correctness property)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b, s, h, d = 1, 128, 2, 16
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    valid = jnp.ones((b, s), bool)
    outs = [dops.decode_attention(q, k, v, valid, bs=s // n,
                                  impl="interpret")
            for n in (1, nsplit)]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_stacked_in_place_qwen2(dtype):
    """The stacked cache read in place at a layer other than 0, at qwen2's
    CORP-pruned head shapes (12 q / 2 kv heads, dq 64, dv 128): lanes at
    pos 0, mid-block and S-1, and lanes whose later blocks are wholly dead,
    against the oracle on the one layer cut out."""
    L, B, S, H, Hkv, dq, dv, bs = 3, 5, 512, 12, 2, 64, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, dq), dtype)
    k = jax.random.normal(ks[1], (L, B, S, Hkv * dq), dtype)
    v = jax.random.normal(ks[2], (L, B, S, Hkv * dv), dtype)
    pos = jnp.asarray([0, bs // 2, S - 1, bs, 2 * bs + 7])
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    layer = 2
    ref = dref.decode_attention(q, k[layer].reshape(B, S, Hkv, dq),
                                v[layer].reshape(B, S, Hkv, dv), valid,
                                scale=0.125)
    pal = dops.decode_attention(q, k, v, valid, layer=jnp.int32(layer),
                                scale=0.125, bs=bs, impl="interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)
    # the ref dispatch slices the same layer: same answer, other record
    from repro import kernels
    before = kernels.RESOLVED.copy()
    out = dops.decode_attention(q, k, v, valid, layer=jnp.int32(layer),
                                scale=0.125, impl="ref")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)
    took = kernels.RESOLVED - before
    assert took[("decode_cache", "sliced")] == L, took


def test_flash_decode_single_layer_is_a_stack_of_one():
    """The per-head single-layer layout (B,S,Hkv,d), as cross-attention
    memory and the kernel tests pass it, equals the same rows stacked
    (1,B,S,Hkv*d) and read at layer 0, at qwen2's head shapes."""
    B, S, H, Hkv, dq, dv = 2, 256, 12, 2, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, dq))
    k = jax.random.normal(ks[1], (B, S, Hkv, dq))
    v = jax.random.normal(ks[2], (B, S, Hkv, dv))
    valid = jnp.arange(S)[None, :] <= jnp.asarray([5, S - 1])[:, None]
    one = dops.decode_attention(q, k, v, valid, bs=64, impl="interpret")
    stacked = dops.decode_attention(
        q, k.reshape(1, B, S, -1), v.reshape(1, B, S, -1), valid,
        layer=jnp.int32(0), bs=64, impl="interpret")
    ref = dref.decode_attention(q, k, v, valid, scale=1 / np.sqrt(dq))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(stacked))
    np.testing.assert_allclose(np.asarray(one), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("arch,kw", [
    ("qwen2-1.5b", {}),                     # one scanned segment
    ("gemma3-1b", {"n_layers": 8}),         # scan + unrolled tail, swa ring
    ("jamba-1.5-large-398b", {}),           # mamba state sliced, attn stacked
    ("seamless-m4t-large-v2", {}),          # decoder stack + cross memory
    ("deepseek-v3-671b", {}),               # MLA latent stack, unrolled
                                            # dense layer + scanned MoE
])
def test_decode_step_kernel_matches_jnp_path(arch, kw):
    """Two decode steps through the kernel (interpret mode), which reads
    every scanned layer's K/V (or MLA latent rows) from the carried stack
    in place, match the jnp path that slices each layer, in logits and in
    every cache leaf."""
    import os
    from helpers import batch_for, tiny_cfg
    from repro.models import build_model
    cfg = tiny_cfg(arch, **kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = batch_for(cfg, B=2, T=8)
    batch = {k: b[k] for k in ("tokens", "frames") if k in b}
    _, cache = model.prefill(params, batch, 16)
    tok = b["tokens"][:, -1:]

    def two_steps():               # a fresh trace: the impl is read then
        step = jax.jit(lambda p, t, c: model.decode_step(p, t, c))
        out, c = [], cache
        for _ in range(2):
            lg, c = step(params, tok, c)
            out.append(lg)
        return out, c

    from repro import kernels
    l0, c0 = two_steps()
    before = kernels.RESOLVED.copy()
    os.environ["REPRO_DECODE_IMPL"] = "interpret"
    try:
        l1, c1 = two_steps()
    finally:
        del os.environ["REPRO_DECODE_IMPL"]
    took = kernels.RESOLVED - before
    assert took[("flash_decode", "interpret")] and \
        not took[("decode_cache", "sliced")], took
    for a, b_ in zip(l0, l1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-4)
    for a, b_ in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-4)


def test_decode_path_uses_kernel_consistently():
    """Model decode with REPRO_DECODE_IMPL=interpret must match the jnp
    path bit-for-bit-ish (kernel wired into attention._decode_sdpa)."""
    import os
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = reduced(get_config("granite-8b")).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    lg, cache = model.prefill(params, {"tokens": toks}, 16)
    step = toks[:, -1:]
    l0, _ = model.decode_step(params, step, cache)
    os.environ["REPRO_DECODE_IMPL"] = "interpret"
    try:
        l1, _ = model.decode_step(params, step, cache)
    finally:
        del os.environ["REPRO_DECODE_IMPL"]
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=1e-4,
                               atol=1e-4)
