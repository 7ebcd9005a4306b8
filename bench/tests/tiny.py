"""A checkout of the benchmark at toy sizes for the CPU tests: the real
``bench/`` code with tiny configurations, traffic and limits in a
temporary root, and the look for a chip steered to a stand-in TPU."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

LM = {"name": "tiny-lm", "family": "lm", "n_layers": 2, "d_model": 64,
      "n_heads": 4, "n_kv_heads": 2, "d_head": 32, "d_ff": 128,
      "vocab_size": 512, "act": "silu", "mlp_kind": "glu",
      "qkv_bias": True, "rope_theta": 10000.0, "tie_embeddings": True,
      "norm_kind": "rmsnorm", "norm_eps": 1e-5, "dtype": "bfloat16",
      "d_ff_kept": 64, "qk_kept": 16}
VIT = {"name": "tiny-vit", "family": "vit", "n_layers": 2, "d_model": 64,
       "n_heads": 4, "n_kv_heads": 4, "d_head": 16, "d_ff": 128,
       "vocab_size": 0, "act": "gelu", "mlp_kind": "plain",
       "qkv_bias": True, "norm_kind": "layernorm", "norm_eps": 1e-5,
       "frontend": "patch_conv", "n_classes": 10, "img_size": 32,
       "patch": 8, "pool": "cls", "dtype": "float32", "d_ff_kept": 64,
       "qk_kept": 8}
SERVE = {"slots": 4, "max_len": 128, "queue_depth": 1000,
         "prefill_chunk": None}
TRAFFIC = {
    "tchat": {"driver": "serve", "kind": "open_loop", "rate": 8.0,
              "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 8, "max": 60},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                         "min": 2, "max": 24},
              "serve": SERVE},
    "tclassify": {"driver": "classify", "kind": "closed_loop", "batch": 4,
                  "pool": 2},
}
# limits at these sizes, between the program's and the control's CPU
# readings on seeds 11-13 (test_control_is_not_correct prints both): logit
# gap 0.0033 and 0.026, relative logit error 3.1e-6 and 0.30
LIMITS = {"tiny-lm.tchat": {"logit_gap": 0.012},
          "tiny-vit.tclassify": {"logit_rel_err": 2e-3}}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "peaks": {"flops": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}}


def make_root(tmp) -> str:
    """A temporary checkout: the real bench code, tiny data files."""
    root = str(tmp)
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cells, configs = [], []
    for m, traffic, ref in ((LM, "tchat", "lm"),
                            (VIT, "tclassify", "vit")):
        f = f"bench/configs/{m['name']}.json"
        with open(os.path.join(root, f), "w") as fh:
            json.dump({"name": m["name"], "reference": ref, "model": m,
                       "reduced": []}, fh)
        configs.append({"name": m["name"], "source": "test", "file": f,
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{m['name']}.{traffic}", "config": m["name"],
                      "traffic": traffic, "chips": 1, "why": "test"})
    for name, t in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as fh:
            json.dump(t, fh)
    for name, lim in LIMITS.items():
        with open(os.path.join(bench, "limits", name + ".json"), "w") as fh:
            json.dump(lim, fh)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    serve = [c["name"] for c in cells if c["traffic"] == "tchat"]
    vit = [c["name"] for c in cells if c["traffic"] == "tclassify"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = vit if "images_per_s" in (m["name"],
                                                      m.get("moves")) \
                else serve
    spec.update(configs=configs, workloads=cells)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return root


def run_cell(root, monkeypatch, workload, seed=7, seconds=2.0, trace=0):
    """``bench/run.py``'s main on ``root`` with the chip look steered to a
    stand-in TPU; kernels may take their CPU implementations."""
    import jax
    import harness
    import run as bench_run

    def device_check(chips):
        return dict(DEVICE, devices=jax.devices()[:chips])

    monkeypatch.setattr(harness, "device_check", device_check)
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    return bench_run.main(argv, root=root,
                          allowed_impls=("pallas", "ref", "xla"))
