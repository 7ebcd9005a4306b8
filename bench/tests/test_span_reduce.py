"""CPU tests of the span reduction: idle gaps put down to host spans, idle
time under the program's spans, span self times, host time per step, the
shared clock, and device time by named scope; and none of it reads
anything from a trace without the program's spans."""
from __future__ import annotations

import os

import pytest

from bench.tests import tiny

import span_reduce  # noqa: E402  (tiny puts bench/ on the path)
import trace_reduce  # noqa: E402
from span_reduce import Span, Trace  # noqa: E402

DEV, MS = "/device:TPU:0", 1e6
EVENTS = [(DEV, line, name, s * MS, d * MS) for line, name, s, d in [
    ("XLA Modules", "jit__decode_impl(3)", 0, 10),
    ("XLA Modules", "jit__prefill_impl(9)", 14, 20),
    ("XLA Modules", "jit__decode_impl(3)", 40, 10),
    ("XLA Ops", "%fusion.1 = f32[8] fusion(%p)", 0, 4),
    ("XLA Ops", "%decode_attention_splits.3 = f32[8] custom-call(%a)", 3,
     7),
    ("XLA Ops", "%flash_attention.2 = f32[8] custom-call(%b)", 14, 18),
    ("XLA Ops", "%copy.4 = f32[8] copy(%flash_attention.2)", 32, 2),
    ("XLA Ops", "%decode_attention_splits.3 = f32[8] custom-call(%a)", 40,
     10)]]


def span(name, s, e, **stats):
    return Span(name, s * MS, e * MS, stats, "python")


SPANS = [
    span("frontend.step", 0, 12),
    span("engine.decode", 0, 11, step=0, lanes=2),
    span("engine.decode.wait", 1, 10.5),
    span("frontend.stream", 11, 12, lanes=2),
    span("frontend.step", 12.5, 36),
    span("sched.admit", 13, 35, rid=7, slot=1, tokens=9),
    span("engine.prefill", 13, 13.5, rid=7, tokens=9, bucket=16),
    span("engine.prefill.wait", 13.5, 34.5),
    span("bench.gc", 35, 39.5, generation=2),
]


def test_gaps_are_put_down_to_the_innermost_span_over_half():
    gaps = span_reduce.idle_gaps(Trace(EVENTS, SPANS))
    # 34-40 ms: bench.gc covers 4.5 of 6; 10-14 ms: only the first step
    # covers half (engine.decode, frontend.stream, sched.admit less)
    assert gaps == [
        ["bench.gc (jit__prefill_impl -> jit__decode_impl)",
         pytest.approx(6e-3)],
        ["frontend.step (jit__decode_impl -> jit__prefill_impl)",
         pytest.approx(4e-3)]]


def test_without_spans_gaps_read_as_trace_reduce_reads_them():
    trace = Trace(EVENTS, [])
    red = trace_reduce.reduce(EVENTS, {})
    assert span_reduce.idle_gaps(trace) == [
        [f"none ({label})", pytest.approx(s)] for label, s in
        red["idle_gaps"]]
    assert span_reduce.idle_under_spans_s(trace) is None
    assert span_reduce.engine_host_ms(trace) is None
    assert span_reduce.frontend_host_ms(trace) is None
    assert span_reduce.scope_ms(trace, "attn", {}) is None


def test_idle_under_program_spans():
    # host in serving spans 0-12 and 12.5-36 ms; device busy 0-10, 14-34:
    # idle 10-12, 12.5-14, 34-36; bench.gc is not the program's
    got = span_reduce.idle_under_spans_s(Trace(EVENTS, SPANS))
    assert got == pytest.approx(5.5e-3)


def test_self_times_and_host_time_per_step():
    trace = Trace(EVENTS, SPANS)
    table = span_reduce.span_table(trace)
    assert table["frontend.step"] == [2, pytest.approx(1.5e-3)]
    assert table["engine.decode"] == [1, pytest.approx(1.5e-3)]
    assert table["sched.admit"] == [1, pytest.approx(0.5e-3)]
    assert table["bench.gc"] == [1, pytest.approx(4.5e-3)]
    assert span_reduce.engine_host_ms(trace) == pytest.approx(1.5)
    # only the step that decodes, less its engine child: 12 - 11 ms
    assert span_reduce.frontend_host_ms(trace) == pytest.approx(1.0)
    assert trace.parent[2] == 1 and trace.parent[1] == 0


def test_decode_programs_inside_their_spans():
    # the first decode runs in its span before the wait ends, the second
    # has no span
    assert span_reduce.decode_on_clock(Trace(EVENTS, SPANS)) == (1, 2)


def test_scope_time_sums_leaf_ops_per_forward():
    ev = [(DEV, line, name, s * MS, d * MS) for line, name, s, d in [
        ("XLA Modules", "jit_forward(1)", 0, 100),
        ("XLA Modules", "jit_forward(1)", 100, 100),
        ("XLA Ops", "while", 0, 90),
        ("XLA Ops", "fusion.1", 0, 30),
        ("XLA Ops", "flash_attention", 30, 30),
        ("XLA Ops", "fusion.2", 60, 30),
        ("XLA Ops", "dot.3", 90, 10),
        ("XLA Ops", "fusion.1", 100, 30),
        ("XLA Ops", "fusion.2", 130, 20)]]
    paths = {"while": "jit(forward)/attn/while",    # holds ops: not counted
             "fusion.1": "jit(forward)/while/body/attn/dot_general",
             "flash_attention": "jit(forward)/while/body/attn/pallas_call",
             "fusion.2": "jit(forward)/while/body/mlp/dot_general",
             "dot.3": "jit(forward)/dot_general"}
    trace = Trace(ev, [])
    assert span_reduce.scope_ms(trace, "attn", paths) == pytest.approx(45.0)
    assert span_reduce.scope_ms(trace, "mlp", paths) == pytest.approx(25.0)
    assert span_reduce.scope_ms(trace, "attn", paths, "jit_other") is None
    assert span_reduce.scope_ms(trace, "attn", {}) is None


def test_op_events_meet_the_compiled_text_by_name_and_shape():
    event = ("%fusion.125 = (f32[256,197]{0,1:T(8,128)S(1)}, f32[256,197,768]"
             "{2,0,1:T(8,128)}) fusion(f32[256,197,768]{2,0,1:T(8,128)} "
             "%get-tuple-element.424), kind=kOutput")
    line = ("  ROOT %fusion.125 = (f32[256,197]{0,1:T(8,128)S(1)}, "
            "f32[256,197,768]{2,0,1:T(8,128)}) fusion(%get-tuple-element.424)"
            ', kind=kOutput, metadata={op_name="jit(forward)/while/body/'
            'attn/add" source_file="x.py"}')
    paths = span_reduce.scope_map(line)
    assert paths == {span_reduce.op_head(event):
                     "jit(forward)/while/body/attn/add"}
    # the same name with another shape is another op
    assert span_reduce.op_head(event.replace("f32[256,197]{", "f32[9]{")) \
        not in paths


def test_scope_map_of_a_tiny_vit_names_attn_and_mlp_ops():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    model = build_model(ModelConfig(**tiny.VIT))
    params = model.init(jax.random.PRNGKey(0))

    def forward(p, x):
        return model.apply(p, {"images": x})

    x = jnp.zeros((2, tiny.VIT["img_size"], tiny.VIT["img_size"], 3))
    text = jax.jit(forward).lower(params, x).compile().as_text()
    paths = span_reduce.scope_map(text)
    for scope in ("attn", "mlp"):
        ops = [n for n, p in paths.items() if span_reduce.in_scope(p, scope)]
        assert any(n.startswith(("dot", "fusion", "convolution"))
                   for n in ops), (scope, ops)


def test_load_reads_program_and_bench_spans_from_a_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.spans import span as program_span
    with jax.profiler.trace(str(tmp_path)):
        with program_span("engine.decode", step=4, lanes=3):
            with TraceAnnotation("bench.sleep"):
                pass
        with TraceAnnotation("not.a.span"):
            pass
    trace = span_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert [(s.name, s.stats) for s in trace.spans] == [
        ("engine.decode", {"step": 4, "lanes": 3}), ("bench.sleep", {})]
    assert trace.parent == [None, 0]


def test_of_run_reads_the_newest_trace_of_its_checkout(tmp_path):
    import jax
    from repro.spans import span as program_span
    for name, cell in (("frontend.step", "a"), ("engine.decode", "b")):
        with jax.profiler.trace(str(tmp_path / ".bench_trace" / cell)):
            with program_span(name):
                pass
    metric = os.path.join(str(tmp_path), "bench", "metrics", "m.py")
    assert span_reduce.of_run({"trace": None}, metric) is None
    trace = span_reduce.of_run({"trace": {"busy_s": 0.0}}, metric)
    assert [s.name for s in trace.spans] == ["engine.decode"]


def test_bench_spans_record_collections_and_sleeps(tmp_path):
    import gc
    import time

    import jax
    import idle_gaps
    sleep = time.sleep
    with jax.profiler.trace(str(tmp_path)):
        with idle_gaps.bench_spans():
            gc.collect()
            time.sleep(1e-3)
    assert time.sleep is sleep and not any(
        getattr(cb, "__name__", "") == "on_gc" for cb in gc.callbacks)
    trace = span_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    names = [s.name for s in trace.spans]
    assert "bench.gc" in names and "bench.sleep" in names


def test_idle_gaps_tool_on_a_tiny_serving_cell(tmp_path, monkeypatch):
    import jax
    import harness
    import idle_gaps
    root = tiny.make_root(tmp_path)
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "off")
    monkeypatch.setattr(harness, "device_check", lambda chips: dict(
        tiny.DEVICE, devices=jax.devices()[:chips]))
    got = idle_gaps.main(["--workload", "tiny-lm.tchat", "--seed", "5",
                          "--seconds", "2"], root=root)
    assert got["spans"]["frontend.step"][0] > 0
    assert got["spans"]["engine.decode.wait"][0] == \
        got["spans"]["engine.decode"][0]
    assert got["decode_on_clock"] == (0, 0)      # no device plane here
    assert got["idle_gaps"] == [] and "itl_p95_ms" in got["end_to_end"]
