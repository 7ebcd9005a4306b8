"""Share of its roofline that ``flash_decode``'s latent kernel (MLA's
absorbed decode) reached in the decode steps of the traced window: the
least time the chip needs for a step (every layer's valid latent rows,
``bench/costs/flash_decode_mla.py``), averaged over the steps the window
dispatched, times the decode steps whose latent-kernel events the trace
holds, over the kernel's device time inside those steps."""
import os

import harness
from peaks import roof_seconds

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "flash_decode_mla.py"))


def read(run):
    spent, steps = (run.get("decode_kernel_s") or {}).get(
        "flash_decode_mla", (0.0, 0))
    if not spent or not run.get("decodes"):
        return None
    m = run["model"]
    a = m["mla"]
    least = 0.0
    for lanes, rows in run["decodes"]:
        f, b = COST.cost(rows, lanes, m["n_heads"], a["kv_lora_rank"],
                         a["qk_rope_dim"], 2)
        least += m["n_layers"] * roof_seconds(f, b, run["peaks"])[0]
    return 100.0 * least / len(run["decodes"]) * steps / spent
