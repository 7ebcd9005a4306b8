"""Config-zoo serving smoke: every LM config in ``repro.configs`` must
admit one request and take two decode steps through the slot engine, and
serve token-identical to its own full-sequence greedy forward.

The zoo spans pure-attn, sliding-window, recurrent (rwkv), hybrid
(jamba), MoE and enc-dec stacks; serving regressions historically hid in
the configs the serve tests didn't cover. The *ragged/prefix* features
are sound exactly where the slot-cache contract is replayable (pure
global-attention KV rewind, or whole-prefix recurrent state snapshots —
docs/serving.md "slot-cache contracts"); the remaining gaps are
``xfail(strict=True)`` entries whose reasons are BUILT from the shared
``repro.serve.errors`` table, so the engine's refusal text and this
matrix cannot drift apart — a silently widening (or narrowing) feature
surface flips a test and forces this file to be updated deliberately.
"""
from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from helpers import greedy_chain_ok, tiny_cfg
from repro.configs import ARCH_IDS, DEIT_IDS
from repro.serve import (PrefixCache, RecurrentSlotCache, ReplicaRouter,
                         ServeEngine, ServeFrontend, Status, cache_contract,
                         slot_specs)
from repro.serve import errors
from repro.models import build_model
from repro.serve.engine import Request

MEM_LEN = 8        # enc-dec encoder-memory length used throughout

# configs with no replayable slot-cache contract (the swa ring buffer is
# neither a rewindable KV nor a whole-prefix recurrent snapshot); the
# xfail reason is the engine's own refusal, formatted from the shared
# error table — literal duplication is rejected by tests/test_serve_errors
PREFIX_GAPS = {
    "gemma3-1b": "prefix_ineligible",
}

# configs that cannot model-shard their slot cache on a 2-way model axis:
# the reduced GQA stacks collapse to a single kv head (and jamba's hybrid
# attn rows with them), so leaf 'k' has no dim divisible by the axis —
# sharding is all-or-nothing, never padded (docs/serving.md "Mesh-sharded
# serving"). The xfail reason is the engine's own refusal, formatted from
# the shared error table, so the refusal text and this matrix cannot
# drift apart.
SHARD_MESH_M = 2
SHARD_GAPS = frozenset({"granite-8b", "gemma3-1b", "qwen2-1.5b",
                        "internvl2-26b", "qwen3-moe-235b-a22b",
                        "jamba-1.5-large-398b"})


def _shard_params():
    return [pytest.param(a, marks=pytest.mark.xfail(
                reason=errors.msg("shard_ineligible",
                                  name=tiny_cfg(a).name, leaf="k",
                                  m=SHARD_MESH_M), strict=True))
            if a in SHARD_GAPS else
            pytest.param(a, marks=pytest.mark.subprocess)
            for a in ARCH_IDS]


def _gap_reason(arch: str, key: str) -> str:
    return errors.msg(key, name=tiny_cfg(arch).name)


def _gap_params(key_for_prefix: str):
    return [pytest.param(a, marks=pytest.mark.xfail(
        reason=_gap_reason(a, key_for_prefix), strict=True))
        if a in PREFIX_GAPS else a for a in ARCH_IDS]


@pytest.fixture(scope="module")
def zoo():
    """Lazy per-arch (model, params) cache shared across this module."""
    built = {}

    def get(arch):
        if arch not in built:
            cfg = tiny_cfg(arch)
            model = build_model(cfg)
            built[arch] = (model, model.init(jax.random.PRNGKey(0)))
        return built[arch]

    return get


def _engine(model, params, n_slots=1, max_len=32):
    kw = dict(n_slots=n_slots, max_len=max_len)
    if model.cfg.family == "encdec":
        kw["mem_len"] = MEM_LEN
    return ServeEngine(model, params, **kw)


def _req(cfg, rid=0, plen=6, gen=3):
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = np.zeros((MEM_LEN, cfg.d_model), np.float32)
    return Request(rid=rid, tokens=(np.arange(plen) % 7 + 1)
                   .astype(np.int32), gen=gen, **kw)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_zoo_one_admit_two_decodes(zoo, arch):
    """The serving floor: admit + 2 decode steps on every LM config."""
    model, params = zoo(arch)
    eng = _engine(model, params)
    eng.begin()
    eng.admit(_req(model.cfg), slot=0)
    assert len(eng.slots[0].out) == 1             # prefill token
    eng.decode_step()
    retired = eng.decode_step()
    assert len(eng.slots[0].out) == 3 and retired == [0]
    comp = eng.retire(0)
    assert comp.tokens.shape == (3,)
    assert all(0 <= t < model.cfg.vocab_size for t in comp.tokens)
    assert eng.slots[0].free


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_zoo_engine_full_forward_parity(zoo, arch):
    """The serving oracle: every config's engine output is token-identical
    to its own full-sequence greedy forward — across mixed prompt/gen
    lengths so slots refill mid-flight (KV, recurrent-state, MoE and
    cross-attn slot paths all covered by the one assertion)."""
    model, params = zoo(arch)
    cfg = model.cfg
    rng = np.random.RandomState(7)
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = rng.randn(MEM_LEN, cfg.d_model).astype(np.float32)
    reqs = [Request(rid=i, tokens=rng.randint(
        0, cfg.vocab_size, size=p).astype(np.int32), gen=g, **kw)
        for i, (p, g) in enumerate([(5, 3), (9, 4), (4, 2)])]
    eng = _engine(model, params, n_slots=2, max_len=32)
    comps = eng.run(reqs)
    assert eng.contract == cache_contract(cfg)
    for req, c in zip(reqs, comps):
        assert len(c.tokens) == req.gen
        assert greedy_chain_ok(model, params, req, c.tokens), req.rid


@pytest.mark.parametrize("arch", _gap_params("prefix_ineligible"))
def test_zoo_prefix_cache_eligibility(zoo, arch):
    """Prefix-cached serving works exactly where the slot-cache contract
    is replayable — pure global-attention KV rewind OR whole-prefix
    recurrent state snapshots (rwkv6/jamba); everywhere else the
    front-end refuses the cache up front (xfail, reason formatted from
    the shared error table)."""
    model, params = zoo(arch)
    eng = _engine(model, params, max_len=48)
    if model.cfg.family == "encdec":
        # eligible-looking stack but excluded: encoder memory keys the
        # cross attention, not the prompt tokens alone
        assert not eng.prefix_eligible()
        pytest.skip("enc-dec is prefix-ineligible by design (cross-attn)")
    fe = ServeFrontend(eng, queue_depth=4, prefix_cache=PrefixCache(),
                       clock=lambda: 0.0)         # raises on gap archs
    shared = (np.arange(8) % 5 + 1).astype(np.int32)
    for i in range(2):
        fe.submit(Request(rid=i, tokens=np.concatenate(
            [shared, np.full((2,), 9 + i, np.int32)]), gen=2))
        while fe.step():
            pass
    assert all(h.status is Status.DONE for h in fe.handles.values())
    assert fe.prefix_cache.hits == 1              # second request reuses
    if eng.contract == "recurrent":
        assert isinstance(eng.slotcache, RecurrentSlotCache)
        assert eng.stats["prefix_hits"] == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_zoo_routed_admit_two_decodes(zoo, arch):
    """The fleet serving floor: every LM config serves one admit + two
    decode steps per replica through a 2-replica router (least-loaded
    spreads the two requests one per replica)."""
    model, params = zoo(arch)
    engines = [_engine(model, params) for _ in range(2)]
    router = ReplicaRouter(engines)
    router.begin(0.0)
    gids = []
    for rid in range(2):
        gid = router.free_slots()[0]
        router.admit(_req(model.cfg, rid=rid), gid)
        gids.append(gid)
    assert [e.active_count() for e in engines] == [1, 1]
    router.decode_step()
    retired = router.decode_step()
    assert sorted(retired) == sorted(gids)
    for gid in retired:
        comp = router.retire(gid)
        assert comp.tokens.shape == (3,)
        assert all(0 <= t < model.cfg.vocab_size for t in comp.tokens)
    assert router.active_count() == 0
    assert all(s.free for e in engines for s in e.slots)


@pytest.mark.parametrize("arch", _shard_params())
def test_zoo_sharded_admit_two_decodes(zoo, arch):
    """The mesh-sharded serving floor over the whole zoo: every LM config
    either takes one sharded admit + two decode steps on a live 2-device
    (1 data x 2 model) mesh, or refuses up front with the single-sourced
    ``shard_ineligible`` message (strict-xfail rows). The deviceless
    ``slot_specs`` call decides both: it raises for every SHARD_GAPS row,
    and for eligible rows the live run happens in a fresh subprocess (the
    forced device count must precede jax init — ``subprocess`` marker)."""
    model, params = zoo(arch)
    sc = _engine(model, params).slotcache
    slot_specs(sc._template, sc.batch_axes, {"model": SHARD_MESH_M},
               name=model.cfg.name,
               kv_heads=sc.kv_heads)      # <- the eligibility decision
    from test_serve_sharded import run_py
    out = run_py(f"""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import ServeEngine, ServeSharding
from repro.serve.engine import Request
from helpers import tiny_cfg

assert len(jax.devices()) == 2
cfg = tiny_cfg({arch!r})
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
kw = dict(n_slots=1, max_len=32)
rkw = {{}}
if cfg.family == "encdec":
    kw["mem_len"] = 8
    rkw["frames"] = np.zeros((8, cfg.d_model), np.float32)
eng = ServeEngine(model, params,
                  sharding=ServeSharding(make_mesh((1, 2))), **kw)
eng.begin()
eng.admit(Request(rid=0, tokens=(np.arange(6) % 7 + 1).astype(np.int32),
                  gen=3, **rkw), slot=0)
assert len(eng.slots[0].out) == 1
eng.decode_step()
retired = eng.decode_step()
assert len(eng.slots[0].out) == 3 and retired == [0]
comp = eng.retire(0)
assert comp.tokens.shape == (3,)
assert all(0 <= t < cfg.vocab_size for t in comp.tokens)
assert eng.slots[0].free
# the decode really ran over a model-split cache, not a replicated one
assert any("model" in tuple(s) for s in jax.tree_util.tree_leaves(
    eng.slotcache.specs, is_leaf=lambda s: isinstance(s, tuple)))
print("OK")
""", devices=2)
    assert "OK" in out


@pytest.mark.parametrize("arch", _gap_params("affinity_ineligible"))
def test_zoo_prefix_affinity_eligibility(zoo, arch):
    """Prefix-affinity routing is constructible exactly where the prefix
    cache is sound (the router refuses it elsewhere — xfail matrix), and
    on eligible stacks the second shared-prefix admit sticks to the warm
    replica."""
    model, params = zoo(arch)
    engines = [_engine(model, params, max_len=48) for _ in range(2)]
    if model.cfg.family == "encdec":
        assert not engines[0].prefix_eligible()
        pytest.skip("enc-dec is prefix-ineligible by design (cross-attn)")
    router = ReplicaRouter(engines, route="prefix-affinity")
    router.begin(0.0)
    shared = (np.arange(8) % 5 + 1).astype(np.int32)
    for i in range(2):
        gid = router.free_slots()[0]
        router.admit(Request(rid=i, tokens=np.concatenate(
            [shared, np.full((2,), 9 + i, np.int32)]), gen=2), gid)
        router.decode_step()
        router.retire(gid)
    # both admits landed on replica 0: the first primed its cache, the
    # second followed the prefix instead of the least-loaded tie-break
    assert engines[0].stats["admits"] == 2
    assert engines[1].stats["admits"] == 0
    assert router.rstats["affinity_hits"] == 1


@pytest.mark.parametrize("arch", ["rwkv6-3b", "jamba-1.5-large-398b"])
def test_zoo_recurrent_slot_bytes_constant(zoo, arch):
    """Recurrent slot state is O(1) in sequence budget: doubling max_len
    must not grow a pure-recurrent stack's per-slot bytes at all, and a
    hybrid's (jamba: attn rows still grow) strictly slower than a
    pure-KV stack's — the serving win the recurrent contract buys;
    bench_serve gates the same invariant with a KV reference column."""
    model, params = zoo(arch)
    small = _engine(model, params, max_len=32)
    large = _engine(model, params, max_len=64)
    assert small.contract == "recurrent"
    growth = large.slotcache.slot_bytes / small.slotcache.slot_bytes
    kv_model, kv_params = zoo("qwen2-1.5b")
    kv_s = _engine(kv_model, kv_params, max_len=32)
    kv_l = _engine(kv_model, kv_params, max_len=64)
    kv_growth = kv_l.slotcache.slot_bytes / kv_s.slotcache.slot_bytes
    if set(model.cfg.layer_kinds) <= {"rwkv", "mamba"}:
        assert growth == 1.0                      # no KV rows at all
    assert growth < kv_growth                     # strictly sublinear
    assert kv_growth > 1.5                        # the KV reference grows


@pytest.mark.parametrize("arch", DEIT_IDS[:1])
def test_vit_has_no_serving_path(arch):
    cfg = tiny_cfg(arch)
    model = build_model(cfg)
    with pytest.raises(ValueError, match=re.escape(
            errors.msg("no_serving_path", name=cfg.name,
                       family=cfg.family))):
        ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                    n_slots=1, max_len=32)
