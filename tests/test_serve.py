"""Continuous-batching serving engine tests (docs/serving.md).

Covers: prefill+decode == full-sequence-forward parity (LM and enc-dec),
zero-sparsity pruned serving token-identity through the engine, slot
admit/retire/refill correctness on a ragged trace, pruned cache shrinkage,
ragged-prefill soundness, and chunked prefill.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve import ServeEngine, synthetic_trace
from repro.serve.engine import Request

from helpers import calib_factory, greedy_chain_ok as _greedy_chain_ok, \
    tiny_cfg


def _lm_cfg():
    return reduced(get_config("qwen2-1.5b")).replace(dtype="float32")


@pytest.fixture(scope="module")
def trained_lm():
    """Briefly trained tiny LM: training sharpens the logits so greedy
    argmax is far from ties and token-equality checks are robust."""
    from repro.launch.train import train
    cfg = _lm_cfg()
    params, _, _ = train(cfg, steps=25, batch=8, seq=32, ckpt_dir=None,
                         peak_lr=2e-3, log=lambda *a: None)
    return cfg, build_model(cfg), params


# ---------------------------------------------------------------------------
# engine parity vs full-sequence forward
# ---------------------------------------------------------------------------

def test_engine_lm_parity_full_forward(trained_lm):
    cfg, model, params = trained_lm
    trace = synthetic_trace(5, cfg.vocab_size, seed=3,
                            prompt_range=(4, 20), gen_range=(2, 8))
    eng = ServeEngine(model, params, n_slots=2, max_len=48)
    comps = eng.run(trace)
    assert eng.ragged_ok           # bucketed ragged prefill exercised
    for req, c in zip(trace, comps):
        assert len(c.tokens) == req.gen
        assert _greedy_chain_ok(model, params, req, c.tokens), req.rid


def test_engine_encdec_parity_full_forward():
    cfg = tiny_cfg("seamless-m4t-large-v2")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    mem = 10
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size,
                                       size=p).astype(np.int32),
                    gen=g,
                    frames=rng.randn(mem, cfg.d_model).astype(np.float32))
            for i, (p, g) in enumerate([(5, 4), (9, 6), (3, 2)])]
    eng = ServeEngine(model, params, n_slots=2, max_len=24, mem_len=mem)
    comps = eng.run(reqs)
    for req, c in zip(reqs, comps):
        assert len(c.tokens) == req.gen
        assert _greedy_chain_ok(model, params, req, c.tokens), req.rid


def test_engine_exact_length_fallback_swa():
    """Sliding-window archs are not ragged-eligible: the engine must fall
    back to exact-length prefill and still match the full forward."""
    cfg = tiny_cfg("gemma3-1b")
    assert "swa" in cfg.layer_kinds
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              size=p).astype(np.int32), gen=g)
            for i, (p, g) in enumerate([(6, 3), (6, 4), (9, 3)])]
    eng = ServeEngine(model, params, n_slots=2, max_len=24)
    assert not eng.ragged_ok
    comps = eng.run(reqs)
    for req, c in zip(reqs, comps):
        assert _greedy_chain_ok(model, params, req, c.tokens), req.rid


# ---------------------------------------------------------------------------
# ragged (bucketed) prefill soundness
# ---------------------------------------------------------------------------

def test_ragged_prefill_matches_exact_prefill():
    """Right-padded prefill with lengths= must produce the same logits and
    an equivalent cache to the exact-length prefill."""
    cfg = _lm_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    P, L, max_len = 11, 16, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, P), 0,
                              cfg.vocab_size)
    padded = jnp.pad(toks, ((0, 0), (0, L - P)))
    lg_exact, cache_exact = model.prefill(params, {"tokens": toks}, max_len)
    lg_ragged, cache_ragged = model.prefill(
        params, {"tokens": padded}, max_len,
        lengths=jnp.full((2,), P, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_exact), np.asarray(lg_ragged),
                               rtol=1e-5, atol=1e-5)
    # decode one step from both caches: identical logits
    nxt = jnp.argmax(lg_exact[:, -1, : cfg.vocab_size],
                     -1)[:, None].astype(jnp.int32)
    d1, _ = model.decode_step(params, nxt, cache_exact)
    d2, _ = model.decode_step(params, nxt, cache_ragged)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


def test_ragged_prefill_rejected_on_swa():
    cfg = tiny_cfg("gemma3-1b")
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="ragged prefill"):
        jax.eval_shape(
            lambda p: model.prefill(
                p, {"tokens": jnp.zeros((1, 8), jnp.int32)}, 16,
                lengths=jnp.full((1,), 4, jnp.int32)), params)


# ---------------------------------------------------------------------------
# slot lifecycle on a ragged trace
# ---------------------------------------------------------------------------

def test_slot_admit_retire_refill(trained_lm):
    cfg, model, params = trained_lm
    trace = synthetic_trace(7, cfg.vocab_size, seed=5,
                            prompt_range=(4, 12), gen_range=(2, 10))
    eng = ServeEngine(model, params, n_slots=2, max_len=32)
    comps = eng.run(trace)
    assert [c.rid for c in comps] == [r.rid for r in trace]
    assert all(len(c.tokens) == r.gen for c, r in zip(comps, trace))
    # with 7 requests over 2 slots, slots MUST have been refilled mid-flight
    assert eng.stats["admits"] == 7
    assert eng.stats["refills"] >= 5
    assert eng.stats["max_concurrent"] == 2
    assert all(s.free for s in eng.slots)
    # refills must not contaminate neighbours: every stream still matches
    # its own full-sequence greedy chain
    for req, c in zip(trace, comps):
        assert _greedy_chain_ok(model, params, req, c.tokens), req.rid


def test_gen_one_request_completes_at_admit(trained_lm):
    cfg, model, params = trained_lm
    reqs = [Request(rid=0, tokens=np.arange(5, dtype=np.int32), gen=1),
            Request(rid=1, tokens=np.arange(7, dtype=np.int32), gen=3)]
    eng = ServeEngine(model, params, n_slots=1, max_len=16)
    comps = eng.run(reqs)
    assert len(comps[0].tokens) == 1 and len(comps[1].tokens) == 3
    for req, c in zip(reqs, comps):
        assert _greedy_chain_ok(model, params, req, c.tokens)


# ---------------------------------------------------------------------------
# pruned serving
# ---------------------------------------------------------------------------

def test_zero_sparsity_pruned_token_identical(trained_lm):
    """CORP at zero sparsity is the identity; the engine must serve the
    'pruned' model token-identically to the dense one."""
    from repro.core import PruneConfig, corp_prune
    cfg, model, params = trained_lm
    pruned, pcfg, _ = corp_prune(model, params, calib_factory(cfg),
                                 PruneConfig(0.0, 0.0))
    trace = synthetic_trace(4, cfg.vocab_size, seed=7,
                            prompt_range=(4, 16), gen_range=(3, 6))
    dense = ServeEngine(model, params, n_slots=2, max_len=32).run(trace)
    served = ServeEngine(build_model(pcfg), pruned,
                         n_slots=2, max_len=32).run(trace)
    for a, b in zip(dense, served):
        assert list(a.tokens) == list(b.tokens)


def test_pruned_config_shrinks_cache():
    """Pruned qk dims shrink the preallocated KV cache — the structured-
    pruning serving payoff the engine exists to exploit."""
    cfg = _lm_cfg()
    pcfg = cfg.pruned(0.5, 0.5)
    assert pcfg.eff_qk < cfg.d_head
    dense = ServeEngine(build_model(cfg),
                        build_model(cfg).init(jax.random.PRNGKey(0)),
                        n_slots=4, max_len=64)
    pruned = ServeEngine(build_model(pcfg),
                         build_model(pcfg).init(jax.random.PRNGKey(0)),
                         n_slots=4, max_len=64)
    assert pruned.cache_bytes < dense.cache_bytes
    # K rows carry the pruned per-head dim
    k_dims = {leaf.shape[-1] for path, leaf in
              jax.tree_util.tree_flatten_with_path(pruned.slotcache.cache)[0]
              if any(getattr(p, "key", None) == "k" for p in path)}
    assert k_dims == {pcfg.eff_qk}


# ---------------------------------------------------------------------------
# chunked prefill (serve/scheduler.py policy over the non-atomic admit)
# ---------------------------------------------------------------------------

def test_chunked_prefill_token_identical(trained_lm):
    """run(prefill_chunk=c) must stream byte-identically to the unchunked
    engine on the same trace, for several chunk sizes."""
    cfg, model, params = trained_lm
    trace = synthetic_trace(5, cfg.vocab_size, seed=11,
                            prompt_range=(4, 20), gen_range=(2, 8))
    base = ServeEngine(model, params, n_slots=2, max_len=48).run(trace)
    for c in (1, 3, 7):
        eng = ServeEngine(model, params, n_slots=2, max_len=48)
        comps = eng.run(trace, prefill_chunk=c)
        for b, ch in zip(base, comps):
            assert b.tokens.tolist() == ch.tokens.tolist(), (c, b.rid)
        assert eng.stats["admits"] == len(trace)
        assert all(s.free and s.pending is None for s in eng.slots)


def test_begin_continue_lifecycle(trained_lm):
    """The non-atomic admit surface: a PREFILLING slot is occupied but
    not decoding; continue_admit without begin_admit is refused from the
    errors table; installation is all-at-once."""
    from repro.serve import ERRORS
    cfg, model, params = trained_lm
    eng = ServeEngine(model, params, n_slots=2, max_len=32)
    eng.begin()
    import re
    with pytest.raises(ValueError,
                       match=re.escape(
                           ERRORS["continue_without_begin"].format(
                               slot=0))):
        eng.continue_admit(0)
    req = Request(rid=0, tokens=np.arange(1, 11, dtype=np.int32), gen=3)
    eng.begin_admit(req, 0)
    assert eng.active_count() == 1 and eng.decoding_count() == 0
    assert not eng.slots[0].free and eng.slots[0].out == []
    steps = 0
    while not eng.continue_admit(0, 3):
        steps += 1
        assert steps < 10
    assert steps > 0 and eng.stats["chunk_steps"] == steps
    assert eng.decoding_count() == 1
    assert len(eng.slots[0].out) == 1       # exactly the prefill token
    base = ServeEngine(model, params, n_slots=1, max_len=32).run([req])
    assert eng.slots[0].out[0] == int(base[0].tokens[0])
    eng.cancel(0)
    assert eng.active_count() == 0


def test_cancel_mid_chunked_prefill_keeps_zero_tokens(trained_lm):
    """Cancelling a PREFILLING slot discards the partial prefill: zero
    tokens kept, the slot is immediately refillable."""
    cfg, model, params = trained_lm
    eng = ServeEngine(model, params, n_slots=1, max_len=32)
    eng.begin()
    eng.begin_admit(Request(rid=0, tokens=np.arange(1, 11, dtype=np.int32),
                            gen=4), 0)
    assert not eng.continue_admit(0, 2)     # mid-prefill
    assert eng.cancel(0) == []
    assert eng.slots[0].free and eng.slots[0].pending is None
    # refill over the same slot still serves exactly
    req = Request(rid=1, tokens=np.arange(2, 8, dtype=np.int32), gen=3)
    comps = eng.run([req])
    assert _greedy_chain_ok(model, params, req, comps[0].tokens)


def test_prefill_stats_keys_are_bounded():
    """Regression: the exact-length fallback used to key prefill stats by
    raw prompt length — one counter per distinct length, an unbounded
    cardinality. Keys must now come from the finite bucket set, on both
    the exact-length fallback (swa) and the ragged path."""
    cfg = tiny_cfg("gemma3-1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              size=p).astype(np.int32),
                    gen=2)
            for i, p in enumerate((3, 5, 6, 7, 9))]   # 5 distinct lengths
    eng = ServeEngine(model, params, n_slots=2, max_len=24)
    assert not eng.ragged_ok                # the exact-length fallback
    eng.run(reqs)
    allowed = {f"prefill_b{b}" for b in eng.buckets}
    seen = {k for k in eng.stats if k.startswith("prefill_b")}
    assert seen and seen <= allowed, (seen, allowed)
    # chunked serving on the same engine family stays bounded too
    eng2 = ServeEngine(model, params, n_slots=2, max_len=24)
    eng2.run(reqs, prefill_chunk=2)
    seen2 = {k for k in eng2.stats if k.startswith("prefill_b")}
    assert seen2 and seen2 <= allowed, (seen2, allowed)
