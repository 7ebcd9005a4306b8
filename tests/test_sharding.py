"""Distribution tests on an 8-device host mesh (subprocess: the device count
must be set before jax initializes, and the main pytest process keeps 1
device per the assignment)."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_py(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # force the host platform: with JAX_PLATFORMS unset, jax probes the TPU
    # backend first, and on TPU-shaped containers without TPU metadata the
    # libtpu GCP metadata fetch retries for ~7 minutes per subprocess before
    # falling back to CPU (the host-device-count flag only applies to CPU).
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pjit_train_step_matches_single_device():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.distrib import sharding as S
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, adamw_init, adamw_update

cfg = reduced(get_config('granite-8b')).replace(dtype='float32', d_model=64)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
ocfg = AdamWConfig()
opt = adamw_init(params, ocfg)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1),(8,16),0,cfg.vocab_size),
         'labels': jax.random.randint(jax.random.PRNGKey(2),(8,16),0,cfg.vocab_size)}

def step(p, o, b):
    loss, g = jax.value_and_grad(lambda pp: model.loss(pp, b))(p)
    np_, no_, _ = adamw_update(p, g, o, 1e-3, ocfg)
    return np_, no_, loss

# single device reference
p1, o1, l1 = jax.jit(step)(params, opt, batch)

mesh = make_mesh((2, 4))
pspec = S.param_specs(params, mesh)
pshard = S.shardings_of(pspec, mesh)
oshard = S.shardings_of(S.param_specs(opt, mesh), mesh)
bshard = S.shardings_of(S.batch_specs(batch, mesh), mesh)
with mesh:
    jstep = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                    out_shardings=(pshard, oshard, None))
    p2, o2, l2 = jstep(jax.device_put(params, pshard),
                       jax.device_put(opt, oshard),
                       jax.device_put(batch, bshard))
print('loss_diff', abs(float(l1) - float(l2)))
d = max(float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(p1), jax.tree.leaves(jax.device_get(p2))))
print('param_diff', d)
assert abs(float(l1) - float(l2)) < 1e-4
assert d < 1e-4
print('OK')
""")
    assert "OK" in out


def test_distributed_corp_matches_single_device():
    """CORP statistics under a (2,4) mesh == single-device statistics:
    the psum-reduced pipeline must produce identical pruned weights."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.core import corp_prune, PruneConfig
from repro.launch.mesh import make_mesh

cfg = reduced(get_config('deit-base')).replace(dtype='float32')
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
def calib():
    for i in range(2):
        yield {'images': jax.random.normal(jax.random.PRNGKey(i), (8, cfg.img_size, cfg.img_size, 3))}
pc = PruneConfig(0.5, 0.5)
p_single, c_single, _ = corp_prune(model, params, calib, pc)
mesh = make_mesh((2, 4))
with mesh:
    p_mesh, c_mesh, _ = corp_prune(model, params, calib, pc)
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p_single), jax.tree.leaves(jax.device_get(p_mesh))))
print('max diff', d)
assert d < 1e-3
print('OK')
""")
    assert "OK" in out


@pytest.mark.subprocess
def test_mini_dryrun_multipod_axes():
    """A (2,2,2) pod/data/model mesh must lower+compile a reduced train step
    (proves the 'pod' axis shards end-to-end)."""
    out = run_py("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.distrib import sharding as S
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, adamw_init, adamw_update

cfg = reduced(get_config('qwen3-moe-235b-a22b')).replace(dtype='float32')
model = build_model(cfg)
params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
ocfg = AdamWConfig()
opt_sds = jax.eval_shape(lambda: adamw_init(params_sds, ocfg))
mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
pshard = S.shardings_of(S.param_specs(params_sds, mesh, fsdp=True), mesh)
oshard = S.shardings_of(S.param_specs(opt_sds, mesh, fsdp=True), mesh)
batch = {'tokens': jax.ShapeDtypeStruct((8, 32), jnp.int32),
         'labels': jax.ShapeDtypeStruct((8, 32), jnp.int32)}

def step(p, o, b):
    loss, g = jax.value_and_grad(lambda pp: model.loss(pp, b))(p)
    np_, no_, _ = adamw_update(p, g, o, 1e-3, ocfg)
    return np_, no_, loss

with mesh:
    lowered = jax.jit(step, in_shardings=(pshard, oshard, None),
                      out_shardings=(pshard, oshard, None)).lower(
        params_sds, opt_sds, batch)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
print('flops', ca.get('flops'))
print('OK')
""")
    assert "OK" in out
