"""Share of its roofline that ``flash_attention`` reached in the prefills
of the traced window: the least time for every layer's causal attention
over each prompt's true length, over the kernel's device time."""
import os

import harness
from peaks import roof_seconds

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "flash_attention.py"))


def read(run):
    red = run.get("trace")
    if not red or not red["kernels"]["flash_attention"][1]:
        return None
    m = run["model"]
    least = 0.0
    for T in run["prefills"]:
        f, b = COST.cost(T, m["n_heads"], m["n_kv_heads"], m["qk_kept"],
                         m["d_head"], True, 2)
        least += m["n_layers"] * roof_seconds(f, b, run["peaks"])[0]
    return 100.0 * least / red["kernels"]["flash_attention"][1]
