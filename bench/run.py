#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up the cell (``setup_s``), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), then the numbers compared beside their
limits under ``checks``. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a traced run. Without a
TPU in ``bench/peaks.py`` it exits non-zero before any cell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate in place of the traffic file's, for "
                         "finding the knee; results under it are not the "
                         "cell's")
    return ap.parse_args(argv)


def prepare(root: str, workload: str):
    """The cell, the devices, and the cell's driver and reference modules;
    exits non-zero without the chip the cell needs."""
    cell = harness.find_cell(root, workload)
    dev = harness.device_check(cell.workload["chips"])
    cache = harness.use_compile_cache(root)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    harness.log(f"[bench] {workload} on {dev['count']} x {dev['kind']} "
                f"({dev['platform']}); compile cache {cache}")
    driver = harness.load_module(os.path.join(
        cell.bench, "drivers", cell.traffic["driver"] + ".py"))
    ref = harness.load_module(os.path.join(
        cell.bench, "reference", cell.config["reference"] + ".py"))
    return cell, dev, driver, ref


def main(argv=None, root: str = ROOT, allowed_impls=("pallas",)):
    args = parse(argv)
    cell, dev, driver, ref = prepare(root, args.workload)
    res = driver.run(cell, ref=ref, dev=dev, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     rate=args.rate, allowed=set(allowed_impls),
                     trace_dir=os.path.join(root, ".bench_trace",
                                            args.workload))
    metrics = harness.read_metrics(
        cell.bench, cell.per_layer if args.trace else cell.end_to_end, res)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    correct = all(c["value"] <= c["limit"] for c in res["checks"].values())
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        red = res["trace"]
        device.update(busy_s=red["busy_s"], window_s=res["trace_window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["compiles_in_window"] = res["compiles_in_window"]
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        harness.log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    out["run"] = res
    return out


if __name__ == "__main__":
    main()
