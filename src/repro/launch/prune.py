"""Distributed CORP pruning driver.

    PYTHONPATH=src python -m repro.launch.prune --arch deit-tiny-reduced \
        --sparsity 0.5 --calib 256 --ckpt-in /tmp/ckpt --out /tmp/pruned

Loads (or initializes) dense params, runs the one-shot CORP pipeline over a
calibration stream, saves the pruned checkpoint + report. With --mesh the
statistics passes run under a device mesh; adding --calib-sharded threads
the mesh into the CalibrationEngine as an explicit sharding contract:
per-unit covariance/Gram blocks column-sharded over the model axis, batch
contributions psum-reduced, no replicated full Sigma on any device. With
--one-traversal the two calibration passes fuse into a single traversal of
the calibration set (speculative pass-2 statistics, docs/pipeline.md).

Every flag is documented in docs/cli.md with a worked end-to-end example.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.core import PruneConfig, corp_prune
from repro.data import calib_stream
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, parse_shape
from repro.launch.train import resolve_config
from repro.models import build_model


def main(argv=None):
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``); returns
    ``(model, dense_params, pruned_params, pruned_cfg, report)``."""
    ap = argparse.ArgumentParser(
        description="One-shot CORP pruning over a calibration stream "
                    "(see docs/cli.md for a worked example)")
    ap.add_argument("--arch", required=True,
                    help="model config name (repro.configs registry), e.g. "
                         "deit-base, granite-8b; '-reduced' suffix shrinks "
                         "it for smoke runs")
    ap.add_argument("--sparsity", type=float, default=0.5,
                    help="fraction of structures to REMOVE from both MLP "
                         "hidden dims and attention qk dims (per-kind "
                         "overrides below win)")
    ap.add_argument("--mlp-sparsity", type=float, default=None,
                    help="override --sparsity for MLP/MoE/mamba hidden "
                         "channels (0 disables MLP pruning)")
    ap.add_argument("--attn-sparsity", type=float, default=None,
                    help="override --sparsity for attention qk dims/rotary "
                         "pairs (0 disables attention pruning)")
    ap.add_argument("--expert-sparsity", type=float, default=0.0,
                    help="fraction of WHOLE routed experts to remove (MoE "
                         "archs only; kept count never drops below top_k; "
                         "removed experts' contributions are ridge-folded "
                         "onto the retained set)")
    ap.add_argument("--calib", type=int, default=128,
                    help="number of calibration samples (unlabeled)")
    ap.add_argument("--calib-batch", type=int, default=8,
                    help="calibration batch size")
    ap.add_argument("--calib-seq", type=int, default=64,
                    help="calibration sequence length (LM archs only)")
    ap.add_argument("--rank-policy", default="combined",
                    help="MLP ranking statistic: act | mag | combined | "
                         "active (repro.core.ranking.mlp_scores)")
    ap.add_argument("--no-compensate", action="store_true",
                    help="rank-only baseline: prune without the closed-form "
                         "ridge compensation (paper ablation)")
    ap.add_argument("--round-to", type=int, default=1,
                    help="round kept counts down to a multiple (TPU lane "
                         "alignment, e.g. 128)")
    ap.add_argument("--lam", type=float, default=1e-4,
                    help="ridge strength, relative to mean(diag(Sigma))")
    ap.add_argument("--ckpt-in", default=None,
                    help="train checkpoint dir to load dense params from "
                         "(newest valid step; fresh init when omitted)")
    ap.add_argument("--out", default=None,
                    help="output dir for the pruned checkpoint + "
                         "report.json (print-only when omitted)")
    ap.add_argument("--mesh", default=None,
                    help="device mesh shape, 'DxM' (data x model) or "
                         "'PxDxM' with a pod axis, e.g. --mesh 2x4; the "
                         "pipeline then runs inside this mesh context")
    ap.add_argument("--calib-sharded", action="store_true",
                    help="shard the calibration statistics over --mesh: "
                         "per-unit covariance/Gram blocks column-sharded "
                         "over the model axis, batch contributions "
                         "psum-reduced — no device holds a full Sigma "
                         "(requires --mesh)")
    ap.add_argument("--calib-ckpt", default=None,
                    help="directory for resumable calibration-statistics "
                         "checkpoints (CalibrationEngine accumulator is "
                         "saved every --calib-ckpt-every batches and the "
                         "pass resumes from the newest valid one)")
    ap.add_argument("--calib-ckpt-every", type=int, default=8,
                    help="batches between calibration checkpoints")
    ap.add_argument("--one-traversal", action="store_true",
                    help="fuse the two calibration passes into ONE "
                         "traversal: pass 1 speculatively accumulates "
                         "pass-2 ridge statistics for top-k candidate "
                         "keep-sets; units whose final keep-set lands "
                         "inside the candidates need no second pass "
                         "(misses fall back to a targeted mini pass 2 — "
                         "see docs/pipeline.md)")
    ap.add_argument("--spec-margin", type=float, default=0.25,
                    help="candidate safety margin for --one-traversal: "
                         "keep_n * margin extra speculative slots per kv "
                         "group (higher = better hit-rate, more "
                         "accumulator memory — (1+margin)^4 for class-1 "
                         "attention)")
    ap.add_argument("--stats-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="dtype activation taps are STREAMED in during "
                         "calibration (bfloat16 halves calibration HBM "
                         "traffic; every statistic still accumulates fp32)")
    ap.add_argument("--gram-tiles", default=None,
                    help="pin the gram kernel tile sizes as 'BF,BN' (e.g. "
                         "128,512) instead of the per-shape roofline "
                         "autotuner (repro.kernels.gram.autotune)")
    args = ap.parse_args(argv)
    if args.calib_sharded and not args.mesh:
        ap.error("--calib-sharded requires --mesh")
    if args.gram_tiles:
        try:
            bf, bn = (int(v) for v in args.gram_tiles.split(","))
        except ValueError:
            ap.error(f"--gram-tiles must be 'BF,BN' ints, "
                     f"got {args.gram_tiles!r}")
        os.environ["REPRO_GRAM_TILES"] = f"{bf},{bn}"

    use_compile_cache()
    cfg = resolve_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_in:
        last = latest_step(args.ckpt_in)
        assert last is not None, f"no checkpoint in {args.ckpt_in}"
        # train checkpoints hold (params, opt_state); restore params only
        (params, _opt), _ = restore_checkpoint(args.ckpt_in, last,
                                               (params, None))
        print(f"[prune] loaded step {last} from {args.ckpt_in}")

    pc = PruneConfig(
        mlp_sparsity=(args.mlp_sparsity if args.mlp_sparsity is not None
                      else args.sparsity),
        attn_sparsity=(args.attn_sparsity if args.attn_sparsity is not None
                       else args.sparsity),
        expert_sparsity=args.expert_sparsity,
        lam=args.lam,
        rank_policy=args.rank_policy,
        compensate=not args.no_compensate,
        round_to=args.round_to,
    )
    stream = calib_stream(cfg, n_samples=args.calib,
                          batch=args.calib_batch, seq=args.calib_seq)

    ctx = make_mesh(parse_shape(args.mesh)) if args.mesh else None
    t0 = time.time()
    kw = dict(progress=print, ckpt_dir=args.calib_ckpt,
              ckpt_every=args.calib_ckpt_every,
              mesh=ctx if args.calib_sharded else None,
              stats_dtype=args.stats_dtype,
              one_traversal=args.one_traversal,
              spec_margin=args.spec_margin)
    if ctx is not None:
        with ctx:
            new_params, new_cfg, report = corp_prune(model, params, stream,
                                                     pc, **kw)
    else:
        new_params, new_cfg, report = corp_prune(model, params, stream, pc,
                                                 **kw)
    dt = time.time() - t0
    print(f"[prune] done in {dt:.1f}s; "
          f"d_ff {cfg.d_ff} -> {new_cfg.eff_d_ff}, "
          f"qk {cfg.qk_full} -> {new_cfg.eff_qk}"
          + (f", experts {cfg.moe.num_experts} -> "
             f"{new_cfg.eff_num_experts}" if cfg.moe is not None else ""))
    if "speculative" in report:
        sp = report["speculative"]
        print(f"[prune] one-traversal: {report['traversals']} traversal(s), "
              f"margin {sp['margin']}, {len(sp['hits'])} hit / "
              f"{len(sp['misses'])} miss"
              + (f" (re-passed: {', '.join(sp['misses'])})"
                 if sp["misses"] else ""))

    if args.out:
        save_checkpoint(args.out, 0, new_params,
                        extra={"config": new_cfg.name,
                               "mlp_sparsity": pc.mlp_sparsity,
                               "attn_sparsity": pc.attn_sparsity,
                               "expert_sparsity": pc.expert_sparsity})
        with open(f"{args.out}/report.json", "w") as f:
            # stacked-layer units report per-layer diagnostic arrays
            json.dump(jax.tree.map(
                lambda x: x.tolist() if hasattr(x, "tolist") else x,
                report["units"]), f, indent=1, default=str)
        print(f"[prune] saved to {args.out}")
    return model, params, new_params, new_cfg, report


if __name__ == "__main__":
    main()
