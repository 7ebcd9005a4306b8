"""(token, held expert) pairs per held expert per MoE layer per decode
step, from the engine's counters over the whole run: how near the
deployment's expert load the cell runs. Only the lanes that hold a
request count; an idle slot's lane routes its stale token uncounted."""


def read(run):
    st = run.get("stats") or {}
    mo = (run.get("model") or {}).get("moe")
    if not st.get("decode_steps") or "moe_pairs" not in st or not mo:
        return None
    m = run["model"]
    layers = m["n_layers"] - m.get("first_k_dense", 0)
    held = mo.get("held") or mo["num_experts"]
    return st["moe_pairs"] / (st["decode_steps"] * held * layers)
