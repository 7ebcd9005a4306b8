#!/usr/bin/env python3
"""Put a traced run's idle gaps down to what the host was doing.

    python3 bench/idle_gaps.py --workload <name> --seed <n> --seconds <s>

Runs one cell once with ``--trace 1`` as ``bench/run.py`` does, and records
the host work that the program's spans cannot see as ``bench.*`` spans:
Python's collections (``bench.gc``, through ``gc.callbacks``) and the load
loop's sleeps (``bench.sleep``). Then prints, as the last line, the longest
idle gaps labelled with the innermost span that covers at least half of
each (``none`` where no span does), every span's count and self time, the
idle time under the program's serving spans, how many decode programs ran
inside their ``engine.decode`` span before its wait ended, and the
traced run's end-to-end metrics. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402


@contextlib.contextmanager
def bench_spans():
    """``bench.gc`` around every collection, ``bench.sleep`` around every
    ``time.sleep``, while the block runs."""
    from jax.profiler import TraceAnnotation
    held = []

    def on_gc(phase, info):
        if phase == "start":
            held.append(TraceAnnotation("bench.gc",
                                        generation=info["generation"]))
            held[-1].__enter__()
        elif held:
            held.pop().__exit__(None, None, None)

    sleep = time.sleep

    def traced_sleep(seconds):
        with TraceAnnotation("bench.sleep"):
            sleep(seconds)

    gc.callbacks.append(on_gc)
    time.sleep = traced_sleep
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        time.sleep = sleep


def explain(trace, window_s: float, top: int = 10) -> dict:
    idle = span_reduce.idle_under_spans_s(trace)
    return {
        "window_s": window_s,
        "idle_gaps": span_reduce.idle_gaps(trace, top),
        "idle_under_program_s": idle,
        "spans": span_reduce.span_table(trace),
        "decode_on_clock": span_reduce.decode_on_clock(trace),
    }


def main(argv=None, root: str = os.path.dirname(HERE)):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with bench_spans():
        out = bench_run.main(["--workload", args.workload, "--seed",
                              str(args.seed), "--seconds", str(args.seconds),
                              "--trace", "1"], root=root)
    trace = span_reduce.load(trace_reduce.find_xplane(
        os.path.join(root, ".bench_trace", args.workload)))
    got = explain(trace, out["device"]["window_s"])
    cell = harness.find_cell(root, args.workload)
    got["end_to_end"] = harness.read_metrics(cell.bench, cell.end_to_end,
                                             out["run"])
    for label, seconds in got["idle_gaps"]:
        harness.log(f"[gaps] {seconds * 1e3:9.3f} ms  {label}")
    for name, (n, own) in sorted(got["spans"].items(),
                                 key=lambda kv: -kv[1][1]):
        harness.log(f"[spans] {name:24s} {n:6d} {own * 1e3:12.3f} ms self")
    print(json.dumps(got), flush=True)
    return got


if __name__ == "__main__":
    main()
