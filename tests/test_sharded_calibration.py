"""Mesh-sharded CalibrationEngine (subprocess: the forced host device count
must be set before jax initializes, as in test_sharding.py).

The sharding contract under test (docs/calibration.md):
  * statistics parity — the sharded engine is a partitioning of the
    single-device engine (linear reductions), allclose in fp32;
  * no replicated Sigma — every dense unit's second moment is column-sharded
    over the model axis (asserted via the accumulator's sharding specs and
    the addressable shard shapes);
  * sharded checkpoint round-trip — gathered-on-save, re-placed per
    ``stat_shardings`` on restore, landing on the uninterrupted sums;
  * foreign-mesh rejection — a checkpoint written under a different mesh
    layout has a different fingerprint and is ignored (fresh start).
"""
from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_py(code: str, devices: int = 4, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep \
        + os.path.dirname(__file__)
    # force CPU: with JAX_PLATFORMS unset, jax probes the TPU backend and
    # on TPU-shaped containers without TPU metadata each subprocess stalls
    # ~7 minutes in libtpu GCP-metadata retries before falling back
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_engine_stat_parity_and_specs():
    """Sharded stats == single-device stats (both passes, fp32 allclose) on
    a forced 4-device (2 data x 2 model) mesh, with every dense unit's s2
    column-sharded — the addressable shard holds F/m columns, never F, so
    a device holds fewer statistic bytes than the unsharded engine — and
    the sharded bf16 stream's Sigma within tolerance of the fp32 one."""
    out = run_py("""
import jax, numpy as np
from repro.core import CalibrationEngine, discover_units
from repro.core.ranking import rank_attn
from repro.models import build_model
from repro.launch.mesh import make_mesh
from helpers import tiny_cfg, calib_factory

assert len(jax.devices()) == 4
def close(a, b, tol=2e-4):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=tol, atol=tol), a, b)

mesh = make_mesh((2, 2))
for arch in ("deit-base", "granite-8b"):
    cfg = tiny_cfg(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    units = discover_units(cfg)
    calib = calib_factory(cfg, n=3)
    ref = CalibrationEngine(model, units, phase=1).run(params, calib())
    eng = CalibrationEngine(model, units, phase=1, mesh=mesh)
    sh = eng.run(params, calib())
    close(sh, ref)

    # no replicated Sigma: dense-unit second moments are model-sharded
    acc = eng.init_stats(params, next(iter(calib())))
    checked = 0
    for u in units:
        if u.kind not in ("mlp", "rwkv_mlp", "mamba"):
            continue
        a = acc[u.name]["s2"]
        spec = tuple(a.sharding.spec)
        assert spec[-1] == "model" and spec[:-1] == (None,) * (a.ndim - 1), \\
            (u.name, spec)
        local = a.addressable_shards[0].data.shape
        assert local[-1] == a.shape[-1] // 2, (u.name, a.shape, local)
        checked += 1
    assert checked, arch
    # so each device holds fewer statistic bytes than one unsharded copy
    per_dev = sum(max(s.data.nbytes for s in x.addressable_shards)
                  for x in jax.tree.leaves(acc))
    assert per_dev < sum(x.nbytes for x in jax.tree.leaves(acc)), arch

    # the bf16 stream composes with sharding: Sigma stays within the
    # documented tolerance (test_bf16_streaming.TOL) of the fp32 stream
    s16 = CalibrationEngine(model, units, phase=1, mesh=mesh,
                            stats_dtype="bfloat16").run(params, calib())
    for u in units:
        if "s2" in sh[u.name]:
            a = np.asarray(sh[u.name]["s2"], np.float64)
            b = np.asarray(s16[u.name]["s2"], np.float64)
            assert np.max(np.abs(a - b)) <= 1e-2 * np.max(np.abs(a)), u.name

    # pass 2 parity (ridge-system inputs; complex classes on granite)
    plan = {}
    for u in units:
        if u.kind in ("attn", "mla", "cross"):
            full = ref[u.name]["rank"].shape[-1]
            plan[u.name] = rank_attn(ref[u.name], max(1, full // 2))
    p2_ref = CalibrationEngine(model, units, phase=2, plan=plan) \\
        .run(params, calib())
    p2_sh = CalibrationEngine(model, units, phase=2, plan=plan,
                              mesh=mesh).run(params, calib())
    close(p2_sh, p2_ref)
    print(arch, "OK")
print("OK")
""")
    assert "OK" in out


def test_sharded_checkpoint_roundtrip_and_foreign_mesh():
    """A sharded pass killed mid-stream resumes from its checkpoint onto
    identical sums; the same directory offered to an engine on a different
    mesh is rejected by fingerprint (fresh start, still correct)."""
    out = run_py("""
import itertools, tempfile
import jax, numpy as np
from repro.core import CalibrationEngine, discover_units
from repro.distrib.fault import CalibrationCheckpointer
from repro.models import build_model
from repro.launch.mesh import make_mesh
from helpers import tiny_cfg, calib_factory

def close(a, b, tol=1e-6):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=tol, atol=tol), a, b)

cfg = tiny_cfg("deit-base")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(5))
units = discover_units(cfg)
calib = calib_factory(cfg, n=4)
mesh_a = make_mesh((2, 2))
mesh_b = make_mesh((1, 4))
eng_a = CalibrationEngine(model, units, phase=1, mesh=mesh_a)
ref = eng_a.run(params, calib())

with tempfile.TemporaryDirectory() as td:
    # die after 2 of 4 batches, checkpointing every batch
    eng_a.run(params, itertools.islice(calib(), 2),
              checkpointer=CalibrationCheckpointer(td, every=1))
    resumed = eng_a.run(params, calib(),
                        checkpointer=CalibrationCheckpointer(td, every=1))
    close(resumed, ref)
    # the resumed accumulator was re-placed sharded before the donated step
    acc = eng_a.init_stats(params, next(iter(calib())))
    acc2, start = CalibrationCheckpointer(td, every=1).restore(
        acc, eng_a.fingerprint, shardings=eng_a.stat_shardings)
    assert start == 4, start
    for u in units:
        if u.kind == "mlp":
            s2 = acc2[u.name]["s2"]
            assert s2.addressable_shards[0].data.shape[-1] \\
                == s2.shape[-1] // 2, s2.sharding
    print("resume OK")

    # foreign mesh: (1,4) layout must not resume a (2,2) checkpoint
    eng_b = CalibrationEngine(model, units, phase=1, mesh=mesh_b)
    assert eng_a.fingerprint != eng_b.fingerprint
    out_b = eng_b.run(params, calib(),
                      checkpointer=CalibrationCheckpointer(td, every=1))
    close(out_b, eng_b.run(params, calib()))
    # and the unsharded engine is a third, distinct identity
    eng_c = CalibrationEngine(model, units, phase=1)
    assert eng_c.fingerprint not in (eng_a.fingerprint, eng_b.fingerprint)
print("OK")
""")
    assert "OK" in out


def test_sharded_corp_prune_functional_parity():
    """End-to-end: corp_prune(mesh=...) and corp_prune_streamed(mesh=...)
    produce models functionally identical to the single-device pipeline
    (weights can differ by the SVD fold's orthogonal ambiguity, outputs
    cannot)."""
    out = run_py("""
import jax, numpy as np
from repro.core import PruneConfig, corp_prune, corp_prune_streamed
from repro.models import build_model
from repro.launch.mesh import make_mesh
from helpers import tiny_cfg, calib_factory, batch_for, out_of

mesh = make_mesh((2, 2))
cfg = tiny_cfg("deit-base")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(2))
calib = calib_factory(cfg, n=4)
pc = PruneConfig(0.5, 0.5)
p_ref, c_ref, _ = corp_prune(model, params, calib, pc)
p_sh, c_sh, _ = corp_prune(model, params, calib, pc, mesh=mesh)
assert c_ref == c_sh
b = batch_for(cfg)
y_ref = np.asarray(out_of(build_model(c_ref), p_ref, b))
y_sh = np.asarray(out_of(build_model(c_sh), p_sh, b))
np.testing.assert_allclose(y_ref, y_sh, rtol=2e-3, atol=2e-3)
p_st, c_st, rep = corp_prune_streamed(model, params, calib, pc, mesh=mesh)
y_st = np.asarray(out_of(build_model(c_st), p_st, b))
np.testing.assert_allclose(y_ref, y_st, rtol=2e-3, atol=2e-3)
print("OK")
""")
    assert "OK" in out


def test_sharded_one_traversal_functional_parity():
    """Composition (docs/pipeline.md): the phase-"1+2" speculative engine
    under a mesh — candidate accumulators take stats_specs shardings, the
    hit path consumes the stream once, and corp_prune(mesh=...,
    one_traversal=True) matches the single-device two-pass pipeline
    functionally."""
    out = run_py("""
import jax, numpy as np
from repro.core import PruneConfig, corp_prune
from repro.models import build_model
from repro.launch.mesh import make_mesh
from helpers import tiny_cfg, calib_factory, batch_for, out_of

mesh = make_mesh((2, 2))
cfg = tiny_cfg("deit-base")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(11))
calib = calib_factory(cfg, n=4)
pc = PruneConfig(0.5, 0.5)
p_ref, c_ref, _ = corp_prune(model, params, calib, pc)
p_one, c_one, rep = corp_prune(model, params, calib, pc, mesh=mesh,
                               one_traversal=True, spec_margin=1.0)
assert c_ref == c_one
assert rep["traversals"] == 1, rep["traversals"]
assert rep["speculative"]["misses"] == []
b = batch_for(cfg)
y_ref = np.asarray(out_of(build_model(c_ref), p_ref, b))
y_one = np.asarray(out_of(build_model(c_one), p_one, b))
np.testing.assert_allclose(y_ref, y_one, rtol=2e-3, atol=2e-3)
print("OK")
""")
    assert "OK" in out
