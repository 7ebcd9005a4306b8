"""Decode steps' model operations in the traced window
(``bench/costs/mla_moe.py``) over the window's length times the chip's
peak. The routed pairs of a step are its lanes' share of the engine's
pair counter, which counts the lanes that hold a request: pairs per
decoding lane over the run, times the step's lanes."""
import os

import harness

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "mla_moe.py"))


def read(run):
    st = run.get("stats") or {}
    if not run.get("trace") or not run["decodes"] or \
            not st.get("decode_lanes") or "moe_pairs" not in st:
        return None
    per_lane = st["moe_pairs"] / st["decode_lanes"]
    flops = sum(COST.decode_flops(run["model"], lanes, rows,
                                  lanes * per_lane)
                for lanes, rows in run["decodes"])
    return 100.0 * flops / (run["trace_window_s"] * run["peaks"]["flops"])
