"""Decode steps' model operations in the traced window over the
window's length times the chip's peak."""
import os

import harness

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "lm.py"))


def read(run):
    if not run.get("trace") or not run["decodes"]:
        return None
    flops = sum(COST.decode_flops(run["model"], lanes, rows)
                for lanes, rows in run["decodes"])
    return 100.0 * flops / (run["trace_window_s"] * run["peaks"]["flops"])
