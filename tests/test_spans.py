"""Host spans on the profiler's clock (``repro.spans``): the serving and
pruning spans land in a ``jax.profiler`` trace, nested as the calls are,
with their stats; stage timings come from the spans; and the table of
names is the one list the program opens spans from and the benchmark
finds programs and kernels by."""
from __future__ import annotations

import glob
import os
import re

import jax
import numpy as np
import pytest

from helpers import calib_factory, tiny_cfg
from repro import spans
from repro.core import PruneConfig, corp_prune
from repro.models import build_model
from repro.serve import ServeEngine, ServeFrontend
from repro.serve.engine import Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def host_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the program's spans, by start."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if spans.NAMES.get(ev.name) == "span":
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def holds(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Spans of a tiny LM served through the front-end with chunked
    prefill (so ``sched.advance`` runs), traced."""
    cfg = tiny_cfg("qwen2-1.5b")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), n_slots=2,
                      max_len=48)
    eng.warmup(prompt_lens=(8, 16), gen=2, prefill_chunk=4)
    fe = ServeFrontend(eng, queue_depth=4, prefill_chunk=4)
    reqs = [Request(rid=10 + i, tokens=(np.arange(9 + i) % 7 + 1)
                    .astype(np.int32), gen=3) for i in range(3)]
    d = str(tmp_path_factory.mktemp("serve_trace"))
    with jax.profiler.trace(d):
        handles = [fe.submit(r) for r in reqs]
        while fe.step():
            pass
    assert all(len(h.tokens) == 3 for h in handles)
    return host_spans(d)


def test_every_serving_span_is_in_the_trace(served):
    serving = {n for n in spans.of_kind("span")
               if n.startswith(("frontend.", "sched.", "engine."))}
    assert serving == {s[0] for s in served}


def test_decode_wait_nests_in_decode_in_the_step(served):
    steps = [s for s in served if s[0] == "frontend.step"]
    decodes = [s for s in served if s[0] == "engine.decode"]
    waits = [s for s in served if s[0] == "engine.decode.wait"]
    assert decodes and len(waits) == len(decodes)
    for w in waits:
        d = [d for d in decodes if holds(d, w)]
        assert len(d) == 1 and any(holds(s, d[0]) for s in steps)
    assert [d[3]["step"] for d in decodes] == list(range(len(decodes)))
    assert all(d[3]["lanes"] >= 1 for d in decodes)


def test_admit_carries_the_request(served):
    admits = [s for s in served if s[0] == "sched.admit"]
    assert sorted(a[3]["rid"] for a in admits) == [10, 11, 12]
    for a in admits:
        assert a[3]["tokens"] == 9 + a[3]["rid"] - 10
        pre = [p for p in served if p[0] == "engine.prefill" and holds(a, p)]
        assert len(pre) == 1 and pre[0][3]["rid"] == a[3]["rid"]
        assert pre[0][3]["tokens"] == 4 and pre[0][3]["bucket"] >= 4


def test_prune_timing_comes_from_the_stage_spans(tmp_path):
    cfg = tiny_cfg("deit-base")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with jax.profiler.trace(str(tmp_path)):
        _, _, report = corp_prune(model, params, calib_factory(cfg, n=2),
                                  PruneConfig(0.5, 0.5))
    assert set(report["timing"]) == {"pass1", "rank", "pass2", "fold"}
    got = host_spans(str(tmp_path))
    for stage, secs in report["timing"].items():
        (s,) = [s for s in got if s[0] == "prune." + stage]
        # the span's own clock and the trace's agree to the annotation
        assert 0 < secs and abs((s[2] - s[1]) * 1e-9 - secs) < 5e-3
    batches = [s for s in got if s[0] == "calib.batch"]
    passes = [s for s in got if s[0] in ("prune.pass1", "prune.pass2")]
    assert len(batches) == 4 and all(any(holds(p, b) for p in passes)
                                     for b in batches)


def test_span_seconds_without_a_trace():
    with spans.span("prune") as whole:
        pass
    assert whole.seconds is not None and whole.seconds >= 0


def test_every_span_opened_in_the_program_is_in_the_table():
    opened = set()
    for path in glob.glob(os.path.join(ROOT, "src", "repro", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            opened |= set(re.findall(r'\bspan\("([^"]+)"', f.read()))
    assert opened == set(spans.of_kind("span"))


def test_program_and_kernel_names_match_the_benchmark():
    """What the benchmark's cell modules and metric readers look for, as
    the table names it: kernels by the function that wraps the Pallas call,
    programs by the jitted function."""
    text = ""
    for sub in ("drivers", "metrics"):
        for path in glob.glob(os.path.join(ROOT, "bench", sub, "*.py")):
            with open(path) as f:
                text += f.read()
    kernels = set(re.findall(r'"(?:flash|expert)_\w+": "(\w+)"', text))
    programs = {"jit_" + fn for fn in
                re.findall(r'program_time\([^,]+, "(\w+)"\)', text)}
    assert kernels == set(spans.of_kind("kernel"))
    assert programs == set(spans.of_kind("program"))
