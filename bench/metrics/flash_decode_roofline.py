"""Share of its roofline that the ``flash_decode`` kernel reached in the
traced window: the least time the chip needs for the decode steps the
window dispatched (every layer's valid cache rows, from their shapes),
over the kernel's device time."""
import os

import harness
from peaks import roof_seconds

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "flash_decode.py"))


def read(run):
    red = run.get("trace")
    if not red or not red["kernels"]["flash_decode"][1]:
        return None
    m = run["model"]
    least = 0.0
    for lanes, rows in run["decodes"]:
        f, b = COST.cost(rows, lanes, m["n_heads"], m["n_kv_heads"],
                         m["qk_kept"], m["d_head"], 2)
        least += m["n_layers"] * roof_seconds(f, b, run["peaks"])[0]
    return 100.0 * least / red["kernels"]["flash_decode"][1]
