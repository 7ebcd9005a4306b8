"""Prefills' model operations (true prompt lengths,
``bench/costs/mla_moe.py``) in the traced window over the window's length
times the chip's peak. A prompt token's routed pairs are taken at the
decode steps' rate, the engine's pair counter over its decoding lanes:
the engine counts no pairs in prefills."""
import os

import harness

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "mla_moe.py"))


def read(run):
    st = run.get("stats") or {}
    if not run.get("trace") or not run["prefills"] or \
            not st.get("decode_lanes") or "moe_pairs" not in st:
        return None
    per_token = st["moe_pairs"] / st["decode_lanes"]
    flops = sum(COST.prefill_flops(run["model"], T, T * per_token)
                for T in run["prefills"])
    return 100.0 * flops / (run["trace_window_s"] * run["peaks"]["flops"])
