"""Quickstart: the paper's pipeline end-to-end on CPU in ~3 minutes.

1. train a small DeiT-family ViT on a synthetic vision task,
2. CORP-prune it 50% (MLP + attention) with closed-form compensation,
3. compare against naive (rank-only) pruning,
4. report Top-1 / parameters / FLOPs — the Table-2 protocol.

Run:  PYTHONPATH=src python examples/quickstart.py [--steps 300]

Calibration under the hood: ``corp_prune`` streams statistics through the
fused ``repro.core.calibrate.CalibrationEngine`` — one jitted step per
calibration batch runs the model once and reduces every unit's statistics
into a donated on-device accumulator (second moments via the Pallas gram
kernel on TPU). The engine is also usable standalone, e.g. to inspect
activation statistics without pruning::

    from repro.core import CalibrationEngine, discover_units
    engine = CalibrationEngine(model, discover_units(model.cfg), phase=1)
    stats = engine.run(params, calib_batches())   # {unit: {n, s1, s2, na}}

Long passes checkpoint + resume via ``corp_prune(..., ckpt_dir=...)``
(see repro.distrib.fault.CalibrationCheckpointer).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.core import PruneConfig, corp_prune  # noqa: E402
from repro.models import build_model  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--sparsity", type=float, default=0.875,
                    help="paper Fig. 2: the compensation gap grows with sparsity")
    args = ap.parse_args()

    from trained import (calib_vit, forward_flops, params_of, trained_vit,
                         vit_eval_acc)

    print("== 1. train (cached under examples/_cache) ==")
    cfg, model, params = trained_vit(args.steps)
    acc0 = vit_eval_acc(model, params)
    p0 = params_of(params)
    print(f"dense model: top1={acc0:.4f} params={p0/1e3:.0f}k")

    print(f"== 2. CORP one-shot prune @ {args.sparsity:.0%} ==")
    pruned, pcfg, report = corp_prune(
        model, params, calib_vit(cfg),
        PruneConfig(args.sparsity, args.sparsity), progress=print)
    m2 = build_model(pcfg)
    acc1 = vit_eval_acc(m2, pruned)

    print("== 3. naive (rank-only) baseline ==")
    naive, ncfg, _ = corp_prune(
        model, params, calib_vit(cfg),
        PruneConfig(args.sparsity, args.sparsity, compensate=False))
    acc2 = vit_eval_acc(build_model(ncfg), naive)

    print("== 4. results ==")
    b = {"images": jax.ShapeDtypeStruct((16, cfg.img_size, cfg.img_size, 3),
                                        jax.numpy.float32)}
    f0 = forward_flops(model, b)
    f1 = forward_flops(m2, b)
    print(f"dense   : top1={acc0:.4f}  params={p0/1e3:7.0f}k  flops=1.00x")
    print(f"CORP    : top1={acc1:.4f}  params={params_of(pruned)/1e3:7.0f}k"
          f"  flops={f1/f0:.2f}x")
    print(f"naive   : top1={acc2:.4f}  (same shape as CORP)")
    print(f"CORP recovers {acc1-acc2:+.4f} Top-1 over naive pruning at "
          f"{args.sparsity:.0%} sparsity — zero gradients, one calibration "
          f"pass ({report['timing']})")


if __name__ == "__main__":
    main()
