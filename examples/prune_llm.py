"""CORP on a language model (the paper's OPT/Table-7 protocol).

Trains a small GQA LM (qwen2-family reduced) on a markov stream, prunes
MLP-only / attention-only / both at 30%, reports perplexity — then shows the
rope-aware class-2 compensator in action (DESIGN.md §2.2).

Run:  PYTHONPATH=src python examples/prune_llm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import PruneConfig, corp_prune  # noqa: E402
from repro.models import build_model  # noqa: E402


def main():
    from trained import calib_lm, lm_eval_ppl, trained_lm
    cfg, model, params = trained_lm()
    print(f"dense ppl = {lm_eval_ppl(model, params):.3f}")
    for tag, (sm, sa) in {"mlp": (0.3, 0.0), "attn": (0.0, 0.3),
                          "both": (0.3, 0.3)}.items():
        for comp in (True, False):
            p, c, rep = corp_prune(model, params, calib_lm(cfg),
                                   PruneConfig(sm, sa, compensate=comp))
            ppl = lm_eval_ppl(build_model(c), p)
            label = "CORP " if comp else "naive"
            print(f"{tag:5s} 30% {label}: ppl={ppl:.3f}")
        if sa > 0:
            # show the per-unit logit-recovery diagnostics (rho^2, Eq. 93)
            rho = [float(v["rho2"].mean()) for k, v in rep["units"].items()
                   if "attn" in k]
            if rho:
                print(f"      mean attention rho^2 (logit energy recovered "
                      f"by kept dims): {sum(rho)/len(rho):.3f}")


if __name__ == "__main__":
    main()
