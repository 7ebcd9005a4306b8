"""Mesh-sharded ServeEngine: deviceless placement rules + live-mesh parity.

Two layers (docs/serving.md "Mesh-sharded serving"):

  * deviceless — ``slot_specs`` / ``device_bytes_estimate`` driven with
    plain ``{axis: size}`` dict meshes over the engines' own structurally
    inferred cache templates (``SlotCache._template`` / ``batch_axes``),
    so the per-leaf placement rules, the batch-1 data-replication rule,
    the all-or-nothing refusal, and the footprint arithmetic all run in
    the plain single-device suite (and the serve-coverage job);
  * subprocess — a forced 4-device (2 data x 2 model) host mesh where the
    sharded engine must stream token-identical to the single-device
    engine for one kv, one recurrent, and one MoE/MLA config on a ragged
    trace (slots refill mid-flight), every live cache leaf's sharding
    equals its ``slot_specs`` spec, measured per-device bytes equal the
    analytic estimate, and the ``--serve-sharded`` CLI path works end to
    end. Subprocess tests carry the registered ``subprocess`` marker so
    ``-m "not subprocess"`` deselects them on minimal hosts.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

import jax
import pytest

from helpers import tiny_cfg
from repro.models import build_model
from repro.serve import (ServeEngine, cache_contract, device_bytes_estimate,
                         slot_specs)
from repro.serve import errors
from repro.serve.sharding import MODEL_DIM_FROM_END, REPLICATED_SLOT_LEAVES

ROOT = os.path.join(os.path.dirname(__file__), "..")
MESH2 = {"data": 2, "model": 2}

# one arch per slot-cache contract family (all shard-eligible reduced)
ELIGIBLE = {"deepseek-7b": "kv", "rwkv6-3b": "recurrent",
            "seamless-m4t-large-v2": "encdec", "deepseek-v3-671b": "kv"}


def run_py(code: str, devices: int = 4, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep \
        + os.path.dirname(__file__)
    # force CPU: without this, jax probes the TPU backend and each
    # subprocess stalls minutes in libtpu metadata retries (see
    # test_sharded_calibration.run_py)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def _engine(arch, n_slots=2, max_len=32):
    cfg = tiny_cfg(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = {"mem_len": 8} if cache_contract(cfg) == "encdec" else {}
    return cfg, ServeEngine(model, params, n_slots=n_slots,
                            max_len=max_len, **kw)


def _leaf_items(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for kp, leaf in flat:
        yield str(getattr(kp[-1], "key", kp[-1])), leaf


# ---------------------------------------------------------------------------
# deviceless: placement rules over real engine templates (dict meshes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(ELIGIBLE))
def test_slot_specs_match_contract_per_leaf(arch):
    """Every leaf of a real engine's cache template lands where its
    contract family says: payload leaves model-sharded on the
    MODEL_DIM_FROM_END dim, bookkeeping leaves model-replicated, and the
    inferred slot axis data-sharded (n_slots=2 divides data=2)."""
    cfg, eng = _engine(arch)
    assert cache_contract(cfg) == ELIGIBLE[arch]
    sc = eng.slotcache
    sp = slot_specs(sc._template, sc.batch_axes, MESH2, name=cfg.name,
                    kv_heads=sc.kv_heads)
    payload = 0
    for (name, leaf), spec, slot_ax in zip(
            _leaf_items(sc._template),
            jax.tree_util.tree_leaves(
                sp, is_leaf=lambda s: isinstance(s, tuple)),
            jax.tree_util.tree_leaves(sc.batch_axes)):
        spec = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        assert spec[slot_ax] == "data", (name, spec)
        if name in REPLICATED_SLOT_LEAVES:
            assert "model" not in spec, (name, spec)
        elif name in MODEL_DIM_FROM_END:
            md = leaf.ndim - MODEL_DIM_FROM_END[name]
            assert spec[md] == "model", (name, spec, md)
            assert leaf.shape[md] % MESH2["model"] == 0
            payload += 1
        else:
            assert "model" not in spec, (name, spec)
    assert payload, f"{arch}: no model-sharded payload leaf"


def test_local_batch1_template_is_data_replicated():
    """The batch-1 prefill template (what scatter-admit places) keeps the
    model split but never the data split — exactly the rule the engine's
    pinned out_shardings rely on."""
    cfg, eng = _engine("deepseek-7b", n_slots=1)
    one = eng.slotcache
    local = slot_specs(one._template, one.batch_axes, MESH2, name=cfg.name,
                       kv_heads=one.kv_heads)
    flat = [tuple(s) for s in jax.tree_util.tree_leaves(
        local, is_leaf=lambda s: isinstance(s, tuple))]
    assert all("data" not in s for s in flat), flat
    assert any("model" in s for s in flat), flat


def test_model_only_mesh_never_touches_slot_axis():
    cfg, eng = _engine("rwkv6-3b")
    sc = eng.slotcache
    sp = slot_specs(sc._template, sc.batch_axes, {"model": 2},
                    name=cfg.name, kv_heads=sc.kv_heads)
    flat = [tuple(s) for s in jax.tree_util.tree_leaves(
        sp, is_leaf=lambda s: isinstance(s, tuple))]
    assert all("data" not in s for s in flat), flat
    assert any("model" in s for s in flat), flat


def test_ineligible_config_refused_never_padded():
    """A reduced GQA config collapsing to one kv head must refuse with the
    single-sourced shard_ineligible message — all-or-nothing, no padding
    (the zoo matrix in test_serve_zoo.py pins the full arch list)."""
    cfg, eng = _engine("granite-8b")
    sc = eng.slotcache
    expect = errors.msg("shard_ineligible", name=cfg.name, leaf="k", m=2)
    with pytest.raises(ValueError, match=re.escape(expect)):
        slot_specs(sc._template, sc.batch_axes, MESH2, name=cfg.name,
                   kv_heads=sc.kv_heads)


def test_device_bytes_estimate_splits_payload_only():
    """Estimate == payload/(d*m) + replicated bookkeeping/d: the only slack
    against a perfect 1/N split is the replicated pos-style leaves."""
    cfg, eng = _engine("deepseek-7b")
    sc = eng.slotcache
    sp = slot_specs(sc._template, sc.batch_axes, MESH2, name=cfg.name,
                    kv_heads=sc.kv_heads)
    est = device_bytes_estimate(sc._template, sp, MESH2)
    total = eng.cache_bytes
    repl = sum(leaf.size * leaf.dtype.itemsize
               for name, leaf in _leaf_items(sc._template)
               if name in REPLICATED_SLOT_LEAVES)
    n_dev = MESH2["data"] * MESH2["model"]
    # payload splits n_dev ways; replicated leaves split only over data
    assert est == (total - repl) // n_dev + repl // MESH2["data"], \
        (est, total, repl)
    assert est < total


def test_degenerate_mesh_is_identity():
    """A (1, 1) mesh runs the entire sharded code path — param placement,
    spec'd cache allocation, pinned out_shardings on decode/prefill/write
    — on the suite's single device, and must stream exactly like the
    unsharded engine (the live multi-device version of this parity is
    the subprocess test below and benchmarks/bench_serve_sharded.py)."""
    from repro.launch.mesh import make_mesh
    from repro.serve import ServeSharding, synthetic_trace
    cfg, ref_eng = _engine("deepseek-7b")
    sharding = ServeSharding(make_mesh((1, 1)))
    assert sharding.sizes == {"data": 1, "model": 1}
    assert sharding.data_size == 1 and sharding.model_size == 1
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shard_eng = ServeEngine(model, params, n_slots=2, max_len=32,
                            sharding=sharding)
    trace = synthetic_trace(4, cfg.vocab_size, seed=5,
                            prompt_range=(4, 8), gen_range=(2, 5))
    for a, b in zip(ref_eng.run(trace), shard_eng.run(trace)):
        assert list(a.tokens) == list(b.tokens), a.rid
    # one device: per-device bytes are total bytes, sharded or not
    assert shard_eng.device_cache_bytes == shard_eng.cache_bytes
    assert ref_eng.device_cache_bytes == ref_eng.cache_bytes
    est = device_bytes_estimate(shard_eng.slotcache._template,
                                shard_eng.slotcache.specs, sharding.sizes)
    assert est == shard_eng.device_cache_bytes


# ---------------------------------------------------------------------------
# live 4-device mesh (subprocess: device count must precede jax init)
# ---------------------------------------------------------------------------

@pytest.mark.subprocess
def test_sharded_engine_token_parity_and_leaf_placement():
    """One kv, one recurrent, one MoE/MLA config on a (2 data x 2 model)
    mesh: sharded streams token-identical to single-device (6 requests
    through 2 slots, so retire/refill happens mid-flight on sharded
    state), every live global-cache leaf carries exactly its slot_specs
    placement, and measured per-device bytes == the analytic estimate."""
    out = run_py("""
import dataclasses
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import (ServeEngine, ServeSharding, device_bytes_estimate,
                         slot_specs, synthetic_trace)
from helpers import tiny_cfg

assert len(jax.devices()) == 4
mesh = make_mesh((2, 2))
sharding = ServeSharding(mesh)

def zoo(arch):
    cfg = tiny_cfg(arch)
    if cfg.moe is not None:   # capacity bump: greedy parity must be exact
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    return cfg

for arch in ("deepseek-7b", "rwkv6-3b", "deepseek-v3-671b"):
    cfg = zoo(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = synthetic_trace(6, cfg.vocab_size, seed=3,
                            prompt_range=(4, 10), gen_range=(2, 6))
    single = ServeEngine(model, params, n_slots=2, max_len=32)
    shard = ServeEngine(model, params, n_slots=2, max_len=32,
                        sharding=sharding)
    ref = single.run(trace)
    got = shard.run(trace)
    assert single.stats["refills"] > 0
    for a, b in zip(ref, got):
        assert list(a.tokens) == list(b.tokens), (arch, a.rid)

    # live placement == slot_specs, per leaf
    sc = shard.slotcache
    specs = jax.tree_util.tree_leaves(
        sc.specs, is_leaf=lambda s: isinstance(s, tuple))
    for leaf, spec in zip(jax.tree_util.tree_leaves(sc.cache), specs):
        assert tuple(leaf.sharding.spec) == tuple(spec), \\
            (arch, leaf.shape, leaf.sharding.spec, spec)
    est = device_bytes_estimate(sc._template, sc.specs, sharding.sizes)
    assert shard.device_cache_bytes == est, \\
        (arch, shard.device_cache_bytes, est)
    assert single.cache_bytes / shard.device_cache_bytes >= 3.6
    print(arch, "OK")
print("OK")
""")
    assert out.count("OK") == 4


@pytest.mark.subprocess
def test_sharded_retire_resets_shard_local_state():
    """Retire/cancel must zero exactly the retired slot's shards: after a
    mixed admit/cancel/retire sequence the sharded cache equals a fresh
    cache wherever slots are free, and a still-running slot's payload is
    untouched by its neighbour's retirement."""
    out = run_py("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import ServeEngine, ServeSharding
from repro.serve.engine import Request
from helpers import tiny_cfg

mesh = make_mesh((2, 2))
cfg = tiny_cfg("rwkv6-3b")   # recurrent: reset-on-retire is load-bearing
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
eng = ServeEngine(model, params, n_slots=2, max_len=32,
                  sharding=ServeSharding(mesh))
eng.begin()
r0 = Request(rid=0, tokens=np.arange(1, 5, dtype=np.int32), gen=8)
r1 = Request(rid=1, tokens=np.arange(2, 8, dtype=np.int32), gen=8)
eng.admit(r0, 0)
eng.admit(r1, 1)
eng.decode_step()
before = jax.tree.map(lambda x: np.asarray(x), eng.slotcache.cache)
eng.cancel(0)                       # shard-local zero-reset of slot 0
after = jax.tree.map(lambda x: np.asarray(x), eng.slotcache.cache)
axes = eng.slotcache.batch_axes
changed = kept = 0
for b, a, ax in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                    jax.tree.leaves(axes)):
    b, a = np.moveaxis(b, ax, 0), np.moveaxis(a, ax, 0)
    np.testing.assert_array_equal(a[1], b[1])     # slot 1 untouched
    assert not a[0].any()                         # slot 0 zeroed
    if b[0].any():
        changed += 1
    kept += 1
assert changed > 0 and kept > 0
eng.decode_step()                   # survivor still decodes fine
print("OK")
""")
    assert "OK" in out


@pytest.mark.subprocess
def test_serve_cli_sharded_end_to_end():
    """--serve-sharded --mesh-shape 2x2 builds the mesh over the four
    host devices the environment provides and reports the per-device
    cache line; --serve-sharded without --mesh-shape is a usage error."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "deepseek-7b-reduced", "--trace", "6", "--slots", "2",
         "--max-len", "48",
         "--prompt-range", "4,10", "--gen-range", "2,6",
         "--serve-sharded", "--mesh-shape", "2x2"],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "sharded over {'data': 2, 'model': 2}" in r.stdout, r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "deepseek-7b-reduced", "--trace", "4", "--serve-sharded"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r2.returncode != 0
    assert "--serve-sharded requires --mesh-shape" in r2.stderr
