"""Prefills' model operations (true prompt lengths) in the traced
window over the window's length times the chip's peak."""
import os

import harness

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "lm.py"))


def read(run):
    if not run.get("trace") or not run["prefills"]:
        return None
    flops = sum(COST.prefill_flops(run["model"], T) for T in run["prefills"])
    return 100.0 * flops / (run["trace_window_s"] * run["peaks"]["flops"])
