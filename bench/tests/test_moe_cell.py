"""CPU rehearsal of the MoE serving cell (``serve_moe`` over a
DeepSeek-V3-shaped decoder holding half its routed experts) at toy size:
a run is correct, the float8 control is not, and neither are the faults
of the timed path that the cell must catch (softmax scoring in place of
the sigmoid, the correction bias left out, the YaRN mscale left out). The
new metric readers and cost files read synthetic runs."""
from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from bench.tests import tiny

import harness  # noqa: E402
import limits_probe  # noqa: E402

CELL = "tiny-dsv3.treason"
DSV3 = {"name": "tiny-dsv3", "family": "lm", "n_layers": 3, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 4, "d_head": 16, "d_ff": 64,
        "vocab_size": 512, "act": "silu", "mlp_kind": "glu",
        "mla": {"q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_dim": 16,
                "qk_rope_dim": 8, "v_dim": 16},
        "moe": {"num_experts": 8, "top_k": 2, "d_expert": 64,
                "num_shared": 1, "scoring": "sigmoid", "n_group": 4,
                "topk_group": 2, "routed_scale": 2.5, "norm_topk": True,
                "held": 4, "first_held": 0},
        "moe_every": 1, "first_k_dense": 1, "dense_d_ff": 128,
        "dense_d_ff_kept": 64, "rope_theta": 10000.0,
        "rope_scaling": {"factor": 40,
                         "original_max_position_embeddings": 64,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                         "mscale_all_dim": 1, "type": "yarn"},
        "tie_embeddings": False, "norm_kind": "rmsnorm", "norm_eps": 1e-6,
        "dtype": "bfloat16", "d_ff_kept": 32, "qk_kept": 8}
TRAFFIC = {"driver": "serve_moe", "kind": "open_loop", "rate": 8.0,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 60},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                      "min": 4, "max": 40},
           "serve": tiny.SERVE}
# between the program's and the control's CPU readings on seeds 11-13
# (test_control_is_not_correct prints both): mean logit gap 9.4e-5-2.8e-3
# and 0.048-0.061; the planted faults read 0.27-0.36
LIMIT = {"logit_gap": 0.012}


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")
    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "bench")
    f = f"bench/configs/{DSV3['name']}.json"
    with open(os.path.join(root, f), "w") as fh:
        json.dump({"name": DSV3["name"], "reference": "deepseek_v3",
                   "model": DSV3, "reduced": []}, fh)
    with open(os.path.join(bench, "traffic", "treason.json"), "w") as fh:
        json.dump(TRAFFIC, fh)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as fh:
        json.dump(LIMIT, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({"name": DSV3["name"], "source": "test",
                            "file": f, "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": DSV3["name"],
                              "traffic": "treason", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-lm.tchat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as fh:
        json.dump(spec, fh)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(root, monkeypatch, trace):
    out = tiny.run_cell(root, monkeypatch, CELL, trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    resolved = out["run"]["stats"]
    assert resolved["moe_pairs"] > 0 and resolved["moe_experts_hit"] > 0
    if trace:
        assert 0 < out["metrics"]["moe.pairs_per_expert"]["value"]
    else:
        assert {"setup_s", "itl_p95_ms"} <= set(out["metrics"])


def test_control_is_not_correct(root, monkeypatch):
    def device_check(chips):
        import jax
        return dict(tiny.DEVICE, devices=jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_check", device_check)
    got, name = limits_probe.readings(root, CELL, [11, 12, 13], 2.0,
                                      allowed=("pallas", "ref", "xla"))
    print(f"{CELL} {name}: limit {LIMIT[name]}, readings {got}")
    for _, prog, ctrl in got:
        assert prog < LIMIT[name] < ctrl


def _fault(monkeypatch, kind):
    import repro.models
    from repro.models import attention
    build = repro.models.build_model
    if kind == "no_mscale":
        scale = attention.mla_scale
        monkeypatch.setattr(attention, "mla_scale", lambda cfg: scale(
            cfg.replace(rope_scaling=None)))
        return
    if kind == "long_rows":
        # a fault confined to the longest rows: decode stops reading the
        # latent cache past row 47, which a few positions of the sample
        # reach
        from repro.kernels.flash_decode import ops
        latent = ops.decode_latent
        monkeypatch.setattr(ops, "decode_latent", lambda q, lat, last, **k:
                            latent(q, lat, jnp.minimum(last, 47), **k))
        return

    def broken(cfg):
        if kind == "softmax":
            return build(cfg.replace(moe=dataclasses.replace(
                cfg.moe, scoring="softmax")))
        model = build(cfg)

        def unbiased(p):
            import jax
            return jax.tree_util.tree_map_with_path(
                lambda kp, a: a * 0 if "router_bias" in str(kp) else a, p)
        return dataclasses.replace(
            model,
            prefill=lambda p, *a, **k: model.prefill(unbiased(p), *a, **k),
            decode_step=lambda p, *a, **k: model.decode_step(
                unbiased(p), *a, **k))
    monkeypatch.setattr(repro.models, "build_model", broken)


@pytest.mark.parametrize("kind", ["softmax", "no_bias", "no_mscale",
                                  "long_rows"])
def test_fault_is_not_correct(root, monkeypatch, kind):
    _fault(monkeypatch, kind)
    out = tiny.run_cell(root, monkeypatch, CELL)
    print(kind, out["checks"])
    assert not out["correct"], out["checks"]


def _reader(name):
    return harness.load_module(os.path.join(tiny.BENCH, "metrics",
                                            name + ".py")).read


def _run():
    """A synthetic traced run of the real cell's shapes: 200 decode steps,
    12 in the window, each of 100 lanes over 100 x 1000 latent rows, and
    two prefills of 256 and 1000 tokens."""
    with open(os.path.join(tiny.BENCH, "configs",
                           "deepseek-v3-corp50.json")) as fh:
        m = json.load(fh)["model"]
    return {"model": m, "peaks": tiny.DEVICE["peaks"],
            "decodes": [(100, 100_000)] * 12, "prefills": [256, 1000],
            "trace_window_s": 1.0,
            "stats": {"decode_steps": 200, "decode_lanes": 20_000,
                      "moe_pairs": 200 * 256, "moe_experts_hit": 200 * 32},
            "trace": {"kernels": {"flash_decode_mla": (60, 0.05),
                                  "flash_attention": (10, 0.01)},
                      "programs": {"jit__decode_impl": (12, 0.24)}},
            "decode_kernel_s": {"expert_gmm": (0.015, 12),
                                "expert_weights": (0.005, 12),
                                "flash_decode_mla": (0.05, 12)}}


def test_readers_on_a_synthetic_run():
    run = _run()
    cost = harness.load_module(os.path.join(tiny.BENCH, "costs",
                                            "flash_decode_mla.py"))
    f, b = cost.cost(100_000, 100, 128, 512, 64, 2)
    assert f == 2 * 128 * 100_000 * 1088
    assert b == (100_000 * 576 + 100 * 128 * 1088) * 2
    # 12 steps x 5 layers of (f, b) at the roofline over 0.05 s
    from peaks import roof_seconds
    want = 100 * 60 * roof_seconds(f, b, run["peaks"])[0] / 0.05
    assert _reader("flash_decode_roofline.mla")(run) == pytest.approx(want)
    # 256 pairs and 32 experts hit per step, 12 steps
    g = harness.load_module(os.path.join(tiny.BENCH, "costs",
                                         "expert_gmm.py"))
    f, b = g.cost(12 * 256, 12 * 32, 7168, 1024, 2)
    want = 100 * roof_seconds(f, b, run["peaks"])[0] / 0.02
    assert _reader("expert_gmm_roofline")(run) == pytest.approx(want)
    assert _reader("moe.pairs_per_expert")(run) == pytest.approx(
        256 / (8 * 4))
    mfu = _reader("mfu.decode.mla_moe")(run)
    assert 0 < mfu < 100
    # the prefills: 5 layers of 128 heads, qk 64 + 64 rope, v 128, over
    # the true lengths, against 0.01 s of the kernel
    fa = harness.load_module(os.path.join(tiny.BENCH, "costs",
                                          "flash_attention.py"))
    want = 100 * 5 * sum(roof_seconds(*fa.cost(T, 128, 128, 128, 128, True,
                                               2), run["peaks"])[0]
                         for T in (256, 1000)) / 0.01
    assert _reader("flash_attention_roofline.prefill.mla")(run) == \
        pytest.approx(want)
    # a prompt token's routed pairs at the decode lanes' rate: 2.56
    c = harness.load_module(os.path.join(tiny.BENCH, "costs", "mla_moe.py"))
    flops = c.prefill_flops(run["model"], 256, 256 * 2.56) + \
        c.prefill_flops(run["model"], 1000, 1000 * 2.56)
    assert _reader("mfu.prefill.mla_moe")(run) == pytest.approx(
        100 * flops / run["peaks"]["flops"])
    # by hand, at toy widths: per token 2 layers x 12 of projections, the
    # dense MLP 6, one MoE layer's router 4 and shared expert 6; causal
    # pairs 3 x 3 dims x 2 layers; one routed pair 6; the head once 6
    toy = {"d_model": 2, "n_heads": 1, "n_layers": 2, "vocab_size": 3,
           "mla": {"q_lora_rank": 1, "kv_lora_rank": 1, "qk_rope_dim": 1,
                   "v_dim": 1},
           "moe": {"num_experts": 2, "num_shared": 1}, "qk_kept": 1,
           "d_ff_kept": 1, "dense_d_ff_kept": 1, "first_k_dense": 1}
    assert c.prefill_flops(toy, 2, 1) == 2 * (2 * 40 + 18 + 6 + 6)
    # a run without the counters or the kernels reads nothing
    bare = dict(run, stats={"decode_steps": 1, "decode_lanes": 1},
                trace={"kernels": {}, "programs": {}}, decode_kernel_s={})
    # a trace that kept the events of only 6 of its 12 steps: the shares
    # count those 6 against their own time
    half = dict(run, decode_kernel_s={"expert_gmm": (0.0075, 6),
                                      "expert_weights": (0.0025, 6),
                                      "flash_decode_mla": (0.025, 6)})
    for name in ("flash_decode_roofline.mla", "expert_gmm_roofline"):
        assert _reader(name)(half) == pytest.approx(_reader(name)(run))
    for name in ("flash_decode_roofline.mla", "expert_gmm_roofline",
                 "moe.pairs_per_expert", "mfu.decode.mla_moe",
                 "flash_attention_roofline.prefill.mla",
                 "mfu.prefill.mla_moe"):
        assert _reader(name)(bare) is None


def test_kernel_time_inside_decode_programs():
    """The expert kernel's time counts only inside the decode programs of
    its own device plane; a second plane adds its own."""
    drv = harness.load_module(os.path.join(tiny.BENCH, "drivers",
                                           "serve_moe.py"))
    mod, ops = "XLA Modules", "XLA Ops"
    t0, t1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        (t0, mod, "jit__decode_impl(3)", 0.0, 100.0),
        (t0, mod, "jit__prefill_impl(4)", 200.0, 100.0),
        (t0, ops, "%gmm.1 = bf16[8] custom-call()", 10.0, 20.0),
        (t0, ops, "%gmm.2 = bf16[8] custom-call()", 250.0, 30.0),
        (t0, ops, "%fusion.3 = bf16[8] fusion(%gmm.1)", 40.0, 50.0),
        (t1, mod, "jit__decode_impl(3)", 0.0, 100.0),
        (t1, ops, "%gmm.1 = bf16[8] custom-call()", 60.0, 5.0),
        (t1, ops, "%gmm.9 = bf16[8] custom-call()", 120.0, 5.0),
    ]
    got = drv.in_program(events, "jit__decode_impl", drv.KERNELS)
    assert got["expert_gmm"] == (pytest.approx(25e-9), 2)
    assert got["flash_decode_mla"] == (0.0, 0)
    # the ops that hand the kernel a layer's expert weights, by shape
    events += [(t0, ops, "%dynamic-slice_fusion.9 = bf16[8,7168,1024]{2,1,0}"
                " fusion(%p)", 50.0, 7.0),
               (t0, ops, "%fusion.4 = bf16[8,1024,7168]{2,1,0} fusion(%p)",
                250.0, 9.0)]
    shapes = drv.expert_weight_shapes({"dtype": "bfloat16", "d_model": 7168,
                                       "d_ff_kept": 1024,
                                       "moe": {"held": 8}})
    assert drv.produced(events, "jit__decode_impl", shapes) == (
        pytest.approx(7e-9), 1)


def test_reference_reads_each_sample_request_once():
    """``serve.run``'s widest gap and ``serve_moe``'s mean come from one
    reference forward per sample request; the control makes its own."""
    import numpy as np
    drv = harness.load_module(os.path.join(tiny.BENCH, "drivers",
                                           "serve_moe.py"))
    calls = []

    class Ref:
        @staticmethod
        def readings(w, m, prompt, served, fp8=False):
            calls.append(fp8)
            return np.full(len(served), 0.5 if fp8 else 0.25), \
                np.zeros(len(served), np.int32)

    ref = drv._Once(Ref)
    res = {"weights": None, "sample": [(np.arange(3), np.arange(4)),
                                       (np.arange(2), np.arange(5))]}
    for p, s in res["sample"]:
        assert float(np.max(ref.gaps(None, {}, p, s))) == 0.25
    gaps, ties = drv.sample_readings(res, ref, {})
    assert gaps.shape == ties.shape == (9,) and calls == [False, False]
    assert drv.control(res, ref, {}) == 0.5 and calls[2:] == [True, True]
