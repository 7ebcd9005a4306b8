"""Model operations of the forwards that ran in the traced window (counted
in the trace) over the window's length times the chip's peak."""
import os

import harness

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "vit.py"))


def read(run):
    if not run.get("trace") or not run["traced_batches"]:
        return None
    flops = COST.image_flops(run["model"]) * run["traced_batches"] \
        * run["batch"]
    return 100.0 * flops / (run["trace_window_s"] * run["peaks"]["flops"])
