"""Share of its roofline that ``flash_attention`` reached in the MLA
prefills of the traced window: the least time for every layer's causal
attention over each prompt's true length, every head with its own key of
``qk_kept`` no-rope plus the shared rope dims and its own value of
``v_dim``, over the kernel's device time."""
import os

import harness
from peaks import roof_seconds

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "flash_attention.py"))


def read(run):
    red = run.get("trace")
    a = (run.get("model") or {}).get("mla")
    if not red or not a or not red["kernels"].get("flash_attention",
                                                  (0, 0.0))[1]:
        return None
    m = run["model"]
    H = m["n_heads"]
    least = 0.0
    for T in run["prefills"]:
        f, b = COST.cost(T, H, H, m["qk_kept"] + a["qk_rope_dim"],
                         a["v_dim"], True, 2)
        least += m["n_layers"] * roof_seconds(f, b, run["peaks"])[0]
    return 100.0 * least / red["kernels"]["flash_attention"][1]
