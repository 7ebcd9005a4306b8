#!/usr/bin/env python3
"""Read the two ends of a cell's correctness limit on the chip, in one
process: for each seed, one short run of the cell at its own load (the
program's reading) and the control's reading on the same sample.

    python bench/limits_probe.py --workload <name> --seconds <s> --seeds 1 2 3

The lower end is the largest program reading over the seeds, the upper the
smallest control reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def readings(root, workload, seeds, seconds, allowed=("pallas",)):
    """[(seed, program reading, control reading)] and the number compared."""
    cell, dev, driver, ref = bench_run.prepare(root, workload)
    (name,) = [k for k in cell.limits]
    out = []
    for seed in seeds:
        res = driver.run(cell, ref=ref, dev=dev, seed=seed, seconds=seconds,
                         trace=False, rate=None, allowed=set(allowed),
                         trace_dir=None)
        prog = res["checks"][name]["value"]
        ctrl = driver.control(res, ref, cell.config["model"])
        harness.log(f"[probe] seed {seed}: program {name} {prog!r}, "
                    f"control {ctrl!r}")
        out.append((seed, prog, ctrl))
        del res
        gc.collect()
    return out, name


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    got, name = readings(bench_run.ROOT, args.workload, args.seeds,
                         args.seconds)
    print(json.dumps({"workload": args.workload, "number": name,
                      "readings": got,
                      "lower": max(p for _, p, _ in got),
                      "upper": min(c for _, _, c in got)}))


if __name__ == "__main__":
    main()
