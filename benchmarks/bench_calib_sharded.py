"""Sharded CalibrationEngine: per-device Sigma footprint + parity gate.

The sharded engine exists for one number: the largest statistic any single
device must hold. Unsharded, every dense unit's second moment is a full
(F, F) fp32 Sigma per device (1.3 GB at d_ff=18432); column-sharded over an
m-way model axis it is (F, F/m). This benchmark builds a forced 4-device
host mesh (2 data x 2 model), runs both engines on the same stream, and

  * asserts fp32 statistic parity (the sharded engine must be a pure
    re-layout of the single-device sums);
  * asserts no accumulator leaf of a dense unit is replicated — the
    addressable shard's trailing dim is F/m, checked from the live arrays;
  * reports per-device resident statistic bytes for both layouts and the
    wall-clock of each pass (host-simulated sharding adds interconnect-free
    collective overhead, so tokens/sec here is NOT the TPU story — the
    footprint column is the point).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python benchmarks/bench_calib_sharded.py
(needs four devices: on the CPU, four host devices from XLA_FLAGS)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

if len(jax.devices()) < 4:
    raise SystemExit("needs 4 devices; on the CPU run with XLA_FLAGS="
                     "--xla_force_host_platform_device_count=4")
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.core import CalibrationEngine, discover_units  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402


def _batches(cfg, n, B, seed=0):
    k = jax.random.PRNGKey(seed)
    return [{"images": jax.random.normal(
        jax.random.fold_in(k, i), (B, cfg.img_size, cfg.img_size, 3))}
        for i in range(n)]


def _device_bytes(acc, sharded: bool) -> int:
    """Largest per-device resident statistic footprint."""
    total = 0
    for leaf in jax.tree.leaves(acc):
        if sharded:
            total += max(s.data.nbytes for s in leaf.addressable_shards)
        else:
            total += np.asarray(leaf).nbytes
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deit-base")
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    args = ap.parse_args()

    assert len(jax.devices()) >= 4, jax.devices()
    cfg = reduced(get_config(args.arch)).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    units = discover_units(cfg)
    batches = _batches(cfg, args.batches, args.batch_size)
    mesh = make_mesh((2, 2))

    single = CalibrationEngine(model, units, phase=1)
    sharded = CalibrationEngine(model, units, phase=1, mesh=mesh)

    def timed(engine):
        t0 = time.perf_counter()
        out = engine.run(params, batches)
        jax.block_until_ready(jax.tree.leaves(out))
        return out, time.perf_counter() - t0

    s_single, t_single = timed(single)
    s_sharded, t_sharded = timed(sharded)

    # parity: the sharded engine is a re-layout, not a re-derivation
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
        s_sharded, s_single)

    # bf16 streaming composes with sharding: per-shard gram_cross ingests
    # bf16 local tiles, accumulators stay fp32 — must hold the same Sigma
    # tolerance as the unsharded bf16 gate (one shared metric + tolerance)
    from benchmarks.bench_calibration import BF16_SIGMA_TOL, sigma_relerr
    sharded_bf16 = CalibrationEngine(model, units, phase=1, mesh=mesh,
                                     stats_dtype="bfloat16")
    s_bf16, t_bf16 = timed(sharded_bf16)
    err = sigma_relerr(s_sharded, s_bf16)
    assert err <= BF16_SIGMA_TOL, (
        f"sharded bf16 stream Sigma relerr {err:.2e} > {BF16_SIGMA_TOL:.0e}")

    # footprint, measured on live accumulators
    acc1 = single.init_stats(params, batches[0])
    acc2 = sharded.init_stats(params, batches[0])
    b_single = _device_bytes(acc1, sharded=False)
    b_sharded = _device_bytes(acc2, sharded=True)
    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    for u in units:
        if u.kind in ("mlp", "rwkv_mlp", "mamba"):
            s2 = acc2[u.name]["s2"]
            local = s2.addressable_shards[0].data.shape
            assert local[-1] == s2.shape[-1] // m, (u.name, local, s2.shape)

    print("name,us_per_call,derived")
    print(f"calib_single_device,{t_single*1e6:.0f},"
          f"{b_single} B/device stats")
    print(f"calib_sharded_2x2,{t_sharded*1e6:.0f},"
          f"{b_sharded} B/device stats "
          f"({b_single/b_sharded:.2f}x smaller, parity OK)")
    print(f"calib_sharded_bf16_stream,{t_bf16*1e6:.0f},"
          f"sigma_relerr={err:.2e} vs fp32-stream sharded (tol 1e-2)")
    assert b_sharded < b_single, (b_sharded, b_single)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
