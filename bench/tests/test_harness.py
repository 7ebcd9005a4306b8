"""CPU tests of the benchmark's yardstick: trace reduction, kernel and model
cost functions, the traffic generator, the peaks table and the harness's
finding of cells, traffic and metrics by name."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench.tests import tiny

import harness  # noqa: E402  (tiny puts bench/ on the path)
import loadgen  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402

COSTS = os.path.join(tiny.BENCH, "costs")


def cost_module(name):
    return harness.load_module(os.path.join(COSTS, name + ".py"))


# ---------------------------------------------------------------------------
# trace reduction

DEV = "/device:TPU:0"
EVENTS = [
    # two decode programs, one prefill; ms -> ns
    (DEV, "XLA Modules", "jit__decode_impl(3)", 0.0, 10e6),
    (DEV, "XLA Modules", "jit__prefill_impl(9)", 14e6, 20e6),
    (DEV, "XLA Modules", "jit__decode_impl(3)", 40e6, 10e6),
    (DEV, "XLA Ops", "%fusion.1 = f32[8] fusion(%p)", 0.0, 4e6),
    # overlaps fusion.1
    (DEV, "XLA Ops", "%decode_attention_splits.3 = f32[8] custom-call(%a)",
     3e6, 7e6),
    # its operand names a kernel, its own name does not
    (DEV, "XLA Ops", "%flash_attention.2 = f32[8] custom-call(%b)", 14e6,
     18e6),
    (DEV, "XLA Ops", "%copy.4 = f32[8] copy(%flash_attention.2)", 32e6, 2e6),
    (DEV, "XLA Ops", "%decode_attention_splits.3 = f32[8] custom-call(%a)",
     40e6, 10e6),
]


def test_reduce_busy_programs_kernels_gaps():
    red = trace_reduce.reduce(EVENTS,
                              {"flash_decode": "decode_attention_splits",
                               "flash_attention": "flash_attention"})
    assert red["busy_s"] == pytest.approx(40e-3)       # 10 + 20 + 10 ms
    assert red["programs"]["jit__decode_impl"] == (2, pytest.approx(20e-3))
    assert red["programs"]["jit__prefill_impl"] == (1, pytest.approx(20e-3))
    assert red["kernels"]["flash_decode"] == (2, pytest.approx(17e-3))
    assert red["kernels"]["flash_attention"] == (1, pytest.approx(18e-3))
    assert red["idle_gaps"][0] == ["jit__prefill_impl -> jit__decode_impl",
                                   pytest.approx(6e-3)]
    assert red["idle_gaps"][1] == ["jit__decode_impl -> jit__prefill_impl",
                                   pytest.approx(4e-3)]
    assert red["device_ops"][0] == [
        "%flash_attention.2 = f32[8] custom-call(%b)", pytest.approx(18e-3)]
    assert trace_reduce.program_time(red, "_decode_impl") == \
        (2, pytest.approx(20e-3))


def test_reduce_averages_busy_over_devices():
    two = EVENTS + [("/device:TPU:1", "XLA Ops", "fusion.2", 0.0, 20e6)]
    assert trace_reduce.reduce(two, {})["busy_s"] == pytest.approx(30e-3)


def test_reduce_empty_trace_reads_nothing():
    red = trace_reduce.reduce([], {"flash_decode": "decode_attention"})
    assert red["busy_s"] == 0.0 and red["kernels"]["flash_decode"] == (0, 0.0)


def test_load_events_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    path = trace_reduce.find_xplane(str(tmp_path))
    host = trace_reduce.load_events(path, planes_like="/host:")
    assert all(len(e) == 5 for e in host)
    assert trace_reduce.load_events(path) == []       # no device planes here


# ---------------------------------------------------------------------------
# cost functions against hand counts

def test_flash_decode_cost_by_hand():
    # 2 lanes, 10 valid rows in all, H=4 q heads over Hkv=2, dq=8, dv=16
    f, b = cost_module("flash_decode").cost(10, 2, 4, 2, 8, 16, 2)
    assert f == 2 * 4 * 10 * (8 + 16)                 # q.k and p.v per row
    assert b == (10 * 2 * (8 + 16) + 2 * 4 * (8 + 16)) * 2


@pytest.mark.parametrize("causal,pairs", [(True, 6), (False, 9)])
def test_flash_attention_cost_by_hand(causal, pairs):
    f, b = cost_module("flash_attention").cost(3, 2, 1, 4, 8, causal, 4,
                                               batch=5)
    assert f == 2 * 5 * 2 * pairs * (4 + 8)
    assert b == 5 * 3 * (2 * 4 + 1 * 4 + 1 * 8 + 2 * 8) * 4


def test_lm_costs_by_hand():
    m = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "qk_kept": 2,
         "d_head": 3, "d_ff_kept": 5, "n_layers": 2, "vocab_size": 7}
    lm = cost_module("lm")
    proj = 4 * (2 * 2 + 2 + 3) + 2 * 3 * 4 + 3 * 4 * 5        # 120
    assert lm.decode_flops(m, 3, 11) == \
        2 * 2 * (3 * proj + 2 * 11 * (2 + 3)) + 2 * 3 * 4 * 7
    assert lm.prefill_flops(m, 4) == \
        2 * 2 * (4 * proj + 2 * 10 * (2 + 3)) + 2 * 4 * 7


def test_vit_cost_by_hand():
    m = {"d_model": 4, "n_heads": 2, "qk_kept": 1, "d_head": 2,
         "d_ff_kept": 3, "n_layers": 1, "n_classes": 5, "img_size": 4,
         "patch": 2}
    N, T, P = 4, 5, 12
    proj = 4 * 2 * (2 * 1 + 2) + 2 * 2 * 4 + 2 * 4 * 3
    want = 2 * (N * P * 4 + (T * proj + 2 * T * T * 3) + 4 * 5)
    assert cost_module("vit").image_flops(m) == want


# ---------------------------------------------------------------------------
# traffic

CHAT = tiny.TRAFFIC["tchat"]


def test_open_loop_is_a_function_of_the_seed():
    a = loadgen.open_loop(CHAT, 2 ** 31 + 5, 10.0, 512)
    b = loadgen.open_loop(CHAT, 2 ** 31 + 5, 10.0, 512)
    assert [(r.due, r.gen, r.prompt.tolist()) for r in a] == \
        [(r.due, r.gen, r.prompt.tolist()) for r in b]


def test_open_loop_seeds_reorder_the_same_work():
    a = loadgen.open_loop(CHAT, 1, 10.0, 512)
    b = loadgen.open_loop(CHAT, 2, 10.0, 512)
    assert len(a) == len(b) == 80                     # rate 8/s for 10 s
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.gen for r in a) == sorted(r.gen for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the gaps, the last one to the window's end included, are one set
    gaps = [np.diff([r.due for r in x] + [10.0]) for x in (a, b)]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    assert a[0].due == 0.0 and a[-1].due < 10.0


def test_open_loop_lengths_follow_their_distribution():
    reqs = loadgen.open_loop(CHAT, 3, 100.0, 512)
    plen = np.array([len(r.prompt) for r in reqs])
    assert plen.min() >= 8 and plen.max() <= 60
    assert np.median(plen) == pytest.approx(24, abs=1)
    gaps = np.diff([r.due for r in reqs])
    assert np.mean(gaps) == pytest.approx(1 / 8.0, rel=0.05)
    assert loadgen.lengths_used(CHAT, 100.0) == sorted(set(plen.tolist()))
    uni = loadgen.quantiles({"dist": "uniform", "min": 10, "max": 20}, 11)
    assert uni.tolist() == list(range(10, 21))


# ---------------------------------------------------------------------------
# peaks and the look for the chip

def test_peaks_refuse_a_non_tpu_platform_and_an_unknown_kind():
    assert peaks.peaks_for("tpu", "TPU v5 lite")["flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu", "cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("tpu", "TPU v9 imaginary")


def test_run_exits_nonzero_before_any_cell_without_a_tpu(tmp_path, capsys):
    import run as bench_run
    root = tiny.make_root(tmp_path)
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "tiny-lm.tchat", "--seed", "1",
                        "--seconds", "1"], root=root)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_roof_seconds_names_its_bound():
    pk = peaks.PEAKS["TPU v5 lite"]
    assert peaks.roof_seconds(197e12, 1.0, pk) == (1.0, "flops")
    assert peaks.roof_seconds(1.0, 819e9, pk) == (1.0, "bytes")


def test_kernel_check_counts_only_the_runs_own_resolutions():
    from repro import kernels
    kernels.note_impl("flash_attention", "interpret")   # an earlier run's
    before = harness.resolutions()
    kernels.note_impl("flash_attention", "pallas")
    ops = ("flash_attention",)
    assert harness.kernel_check({"pallas"}, ops, before)[0] == 0
    assert harness.kernel_check({"pallas"}, ops + ("flash_decode",),
                                before)[0] == 1         # never resolved
    kernels.note_impl("flash_attention", "xla")
    assert harness.kernel_check({"pallas"}, ops, before)[0] == 1


# ---------------------------------------------------------------------------
# finding by name: a new cell is new files plus a new entry

def test_harness_finds_an_added_cell_traffic_and_metric(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "traffic", "added.json"), "w") as f:
        json.dump(dict(CHAT, rate=3.0), f)
    with open(os.path.join(bench, "limits", "tiny-lm.added.json"), "w") as f:
        json.dump({"logit_gap": 0.5}, f)
    with open(os.path.join(bench, "metrics", "added.count.py"), "w") as f:
        f.write("def read(run):\n    return len(run['ttft_s'])\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-lm.added", "config": "tiny-lm",
                              "traffic": "added", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "added.count", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "loadgen", "moves": "itl_p95_ms",
                              "workloads": ["tiny-lm.added"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    cell = harness.find_cell(root, "tiny-lm.added")
    assert cell.traffic["rate"] == 3.0
    assert cell.limits == {"logit_gap": 0.5}
    assert cell.config["model"]["name"] == "tiny-lm"
    assert [m["name"] for m in cell.per_layer] == ["added.count"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    got = harness.read_metrics(bench, cell.per_layer, {"ttft_s": [1, 2]})
    assert got == {"added.count": {"value": 2.0, "unit": "requests"}}


def test_every_declared_metric_has_a_reader_and_every_cell_its_files():
    root = os.path.dirname(tiny.BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(tiny.BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in spec["workloads"]:
        cell = harness.find_cell(root, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
