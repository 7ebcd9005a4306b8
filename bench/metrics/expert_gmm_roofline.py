"""Share of its roofline that the held experts' grouped matmul reached in
the decode steps of the traced window: the least time their pairs and the
experts they hit need (``bench/costs/expert_gmm.py``; per step, the
engine's counters over the whole run, which count only the lanes that
hold a request: an idle lane's pairs are work nobody needs), times the
decode steps whose expert-kernel events the trace holds, over the device
time inside those steps of the expert kernel and of the ops that hand it
each layer's expert weights (``decode_kernel_s["expert_weights"]``): the
kernel reads the weights from where those ops put them, so its own time
leaves out their trip from HBM."""
import os

import harness
from peaks import roof_seconds

COST = harness.load_module(os.path.join(os.path.dirname(__file__), "..",
                                        "costs", "expert_gmm.py"))


def read(run):
    kernel = run.get("decode_kernel_s") or {}
    spent, steps = kernel.get("expert_gmm", (0.0, 0))
    st = run.get("stats") or {}
    if not spent or not st.get("decode_steps") or "moe_pairs" not in st:
        return None
    spent += kernel.get("expert_weights", (0.0, 0))[0]
    per = steps / st["decode_steps"]
    m = run["model"]
    f, b = COST.cost(st["moe_pairs"] * per, st["moe_experts_hit"] * per,
                     m["d_model"], m["d_ff_kept"], 2)
    return 100.0 * roof_seconds(f, b, run["peaks"])[0] / spent
