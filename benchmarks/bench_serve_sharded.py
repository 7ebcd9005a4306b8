"""Mesh-sharded ServeEngine: cross-device parity + per-device footprint.

The live-mesh mirror of ``bench_serve.py``'s analytic 671B gate: a forced
4-device host mesh (2 data x 2 model), one engine per contract family
(kv = deepseek-7b, recurrent = rwkv6-3b, MoE/MLA = deepseek-v3-671b, all
reduced), and three gates:

  * token parity — the sharded engine must stream token-identical to the
    single-device engine on the same ragged trace (slots refill
    mid-flight, so the sharded scatter-admit and shard-local resets are
    both on the hook);
  * measured == analytic footprint — every engine's live per-device
    slot-cache bytes (max addressable shard per leaf) must EXACTLY equal
    ``device_bytes_estimate`` of its specs, and sit at ~1/(data*model)
    of the unsharded cache (replicated ``pos`` bookkeeping is the only
    slack);
  * pruned < dense per device — 50% CORP pruning must shrink the kv
    config's per-device cache strictly below the dense sharded one
    (``eff_qk`` composes with the 1/N model split).

The tok/s column is reported, not gated: host-simulated sharding pays
interconnect-free collective overhead, so decode speed here is NOT the
TPU story — the parity and footprint columns are the point (same stance
as benchmarks/bench_calib_sharded.py).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python benchmarks/bench_serve_sharded.py \
          --table-out sharded_serve.md
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

from benchmarks.bench_serve import _zoo_cfg  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import (ServeEngine, ServeSharding,  # noqa: E402
                         device_bytes_estimate, synthetic_trace)
from repro.serve.engine import format_table  # noqa: E402

SLOTS = 2
MAX_LEN = 48
ARCHS = ("deepseek-7b", "rwkv6-3b", "deepseek-v3-671b")


def _timed_run(eng, trace):
    eng.warmup(prompt_lens=[len(r.tokens) for r in trace])
    t0 = time.perf_counter()
    comps = eng.run(trace)
    return comps, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--table-out", default=None,
                    help="write the footprint + scaling markdown table "
                         "here (CI uploads it as an artifact)")
    args = ap.parse_args()

    assert len(jax.devices()) >= 4, jax.devices()
    mesh = make_mesh((2, 2))
    sharding = ServeSharding(mesh)
    n_dev = sharding.data_size * sharding.model_size

    rows = []
    for arch in ARCHS:
        cfg = _zoo_cfg(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(11)
        trace = synthetic_trace(6, cfg.vocab_size, seed=int(rng.randint(99)),
                                prompt_range=(4, 12), gen_range=(2, 8))
        single = ServeEngine(model, params, n_slots=SLOTS, max_len=MAX_LEN)
        shard = ServeEngine(model, params, n_slots=SLOTS, max_len=MAX_LEN,
                            sharding=sharding)
        comps_1, wall_1 = _timed_run(single, trace)
        comps_s, wall_s = _timed_run(shard, trace)

        # gate: token-identical streams (mid-flight retire/refill included:
        # 6 requests through 2 slots)
        for a, b in zip(comps_1, comps_s):
            assert list(a.tokens) == list(b.tokens), (
                f"{arch}: sharded stream diverged on rid {a.rid}")
        assert single.stats["refills"] > 0, "trace never refilled a slot"

        # gate: live per-device bytes == analytic estimate of the specs
        est = device_bytes_estimate(shard.slotcache._template,
                                    shard.slotcache.specs, sharding.sizes)
        assert shard.device_cache_bytes == est, (
            f"{arch}: measured per-device bytes {shard.device_cache_bytes}"
            f" != analytic {est}")
        split = single.cache_bytes / shard.device_cache_bytes
        assert split >= 0.9 * n_dev, (
            f"{arch}: per-device cache only {split:.2f}x smaller on a "
            f"{n_dev}-device mesh")

        total = sum(len(c.tokens) for c in comps_s)
        rows.append({"arch": cfg.name, "contract": shard.contract,
                     "cache_kb": single.cache_bytes / 1e3,
                     "per_device_kb": shard.device_cache_bytes / 1e3,
                     "split": split,
                     "tok_per_s_single": total / max(wall_1, 1e-9),
                     "tok_per_s_sharded": total / max(wall_s, 1e-9)})
        print(f"[bench_serve_sharded] GATE parity {arch}: "
              f"{len(comps_s)} streams token-identical, per-device "
              f"{shard.device_cache_bytes / 1e3:.1f} kB = analytic, "
              f"{split:.2f}x split")

    # gate: CORP pruning shrinks the per-device cache strictly further
    cfg = _zoo_cfg("deepseek-7b")
    pcfg = cfg.pruned(0.5, 0.5)
    dense = ServeEngine(build_model(cfg),
                        build_model(cfg).init(jax.random.PRNGKey(0)),
                        n_slots=SLOTS, max_len=MAX_LEN, sharding=sharding)
    pruned = ServeEngine(build_model(pcfg),
                         build_model(pcfg).init(jax.random.PRNGKey(0)),
                         n_slots=SLOTS, max_len=MAX_LEN, sharding=sharding)
    assert pruned.device_cache_bytes < dense.device_cache_bytes, (
        f"pruned per-device cache not smaller: "
        f"{pruned.device_cache_bytes} >= {dense.device_cache_bytes}")
    rows.append({"arch": f"{pcfg.name}", "contract": pruned.contract,
                 "cache_kb": pruned.cache_bytes / 1e3,
                 "per_device_kb": pruned.device_cache_bytes / 1e3,
                 "split": dense.cache_bytes / pruned.device_cache_bytes,
                 "tok_per_s_single": float("nan"),
                 "tok_per_s_sharded": float("nan")})
    print(f"[bench_serve_sharded] GATE pruned < dense per device: "
          f"{pruned.device_cache_bytes / 1e3:.1f} < "
          f"{dense.device_cache_bytes / 1e3:.1f} kB "
          f"(eff_qk {cfg.eff_qk} -> {pcfg.eff_qk} on top of the "
          f"1/{sharding.model_size} model split)")

    table = format_table(rows)
    print(table)
    if args.table_out:
        with open(args.table_out, "w") as f:
            f.write("# Mesh-sharded serving (2 data x 2 model forced host "
                    "mesh)\n\nPer-device slot-cache footprint and decode "
                    "scaling; tok/s is host-simulated (collective overhead "
                    "without an interconnect) — the footprint and parity "
                    "columns are the gated story.\n\n" + table + "\n")
        print(f"[bench_serve_sharded] table -> {args.table_out}")
    print("[bench_serve_sharded] all gates passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
