"""Share of its roofline that ``flash_attention`` reached in the ViT
forwards of the traced window: the least time for each of the kernel's
calls there (one layer's bidirectional attention over a batch), over the
kernel's device time."""
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
    T = (m["img_size"] // m["patch"]) ** 2 + 1
    f, b = COST.cost(T, m["n_heads"], m["n_heads"], m["qk_kept"],
                     m["d_head"], False, 4, batch=run["batch"])
    calls, busy = red["kernels"]["flash_attention"]
    return 100.0 * calls * roof_seconds(f, b, run["peaks"])[0] / busy
