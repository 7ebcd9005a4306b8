"""End-to-end serving example: prune a trained LM with CORP, then serve a
ragged request trace through the continuous-batching engine, comparing dense
vs pruned latency percentiles and throughput — the paper's Table-5
efficiency protocol on the serving path (docs/serving.md).

Run:  PYTHONPATH=src python examples/serve_pruned.py [--requests 16]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import PruneConfig, corp_prune  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import (ServeEngine, percentile_table,  # noqa: E402
                         synthetic_trace)
from repro.serve.engine import format_table  # noqa: E402


def serve(model, params, trace, *, slots, max_len):
    eng = ServeEngine(model, params, n_slots=slots, max_len=max_len)
    eng.warmup(prompt_lens=[len(r.tokens) for r in trace])
    t0 = time.perf_counter()
    comps = eng.run(trace)
    table = percentile_table(comps, time.perf_counter() - t0)
    table["cache_kb"] = eng.cache_bytes / 1e3
    return comps, table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--sparsity", type=float, default=0.5)
    args = ap.parse_args()

    from trained import calib_lm, trained_lm
    cfg, model, params = trained_lm()
    prompt_range, gen_range = (8, 48), (4, 48)
    trace = synthetic_trace(args.requests, cfg.vocab_size, seed=0,
                            prompt_range=prompt_range, gen_range=gen_range)

    print(f"== dense serving ({args.requests} ragged requests, "
          f"{args.slots} slots) ==")
    _, t0r = serve(model, params, trace, slots=args.slots,
                   max_len=args.max_len)

    print(f"== CORP prune @ {args.sparsity:.0%} ==")
    pruned, pcfg, _ = corp_prune(model, params, calib_lm(cfg),
                                 PruneConfig(args.sparsity, args.sparsity))
    print("== pruned serving ==")
    _, t1r = serve(build_model(pcfg), pruned, trace, slots=args.slots,
                   max_len=args.max_len)

    t0r["model"], t1r["model"] = "dense", f"pruned {args.sparsity:.0%}"
    keys = ["model", "tokens", "tok_per_s", "lat_p50_ms", "lat_p99_ms",
            "ttft_p50_ms", "ttft_p99_ms", "cache_kb"]
    print(format_table([t0r, t1r], keys))
    print(f"decode speedup {t1r['tok_per_s'] / max(t0r['tok_per_s'], 1e-9):.2f}x, "
          f"KV cache {t0r['cache_kb'] / max(t1r['cache_kb'], 1e-9):.2f}x smaller "
          f"(qk {cfg.d_head} -> {pcfg.eff_qk} shrinks every slot's K rows)")


if __name__ == "__main__":
    main()
