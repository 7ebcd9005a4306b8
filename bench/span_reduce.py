"""From a profiler trace's host spans and op scopes to the numbers the span
metrics read, beside what ``trace_reduce`` reads from the device alone.

The program opens host spans (``repro.spans``: ``frontend.step``,
``engine.decode``, ...) as ``TraceAnnotation``s. They land on the trace's
host plane, one line per thread, on the clock of the device planes'
programs and ops, with their stats (``rid``, ``slot``, ...); a span's
parent is the span that holds it on its line. Spans named ``bench.*`` are
the benchmark's own. The blocks run their halves under
``jax.named_scope`` (``attn``, ``mlp``), which the compiled module's ops
carry in their metadata; a TPU's op events carry no such path, so it is
read from the program's compiled text and matched to the events by op
name and shape. A program that opens no spans and names no scopes (a
checkout from before them) gives none, and every reading here is then
None.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import statistics

import trace_reduce
from trace_reduce import MODULES, OPS

SERVING = ("frontend.", "sched.", "engine.")     # the program's serving spans
Span = collections.namedtuple("Span", "name start end stats line")


def span_names() -> frozenset:
    """The program's span names, none where it has no ``repro.spans``."""
    try:
        from repro import spans
    except ImportError:
        return frozenset()
    return frozenset(spans.of_kind("span"))


class Trace:
    """One trace: ``events`` as ``trace_reduce`` has them and the host
    ``spans`` with each one's ``parent`` index (None at the top);
    ``paths`` keeps the scope map of the program it ran, once read."""

    def __init__(self, events, spans):
        self.events, self.spans = events, spans
        self.parent = nest(spans)
        self.paths = None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    names = span_names()
    events, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        device = "/device:" in plane.name
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                if device:
                    events.append((plane.name, line.name, ev.name,
                                   float(ev.start_ns),
                                   float(ev.duration_ns)))
                elif ev.name in names or ev.name.startswith("bench."):
                    spans.append(Span(ev.name, float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns),
                                      dict(ev.stats), line.name))
    return Trace(events, spans)


_LOADED: dict = {}


def of_run(run: dict, metric_file: str):
    """The trace of a traced run of ``bench/run.py``, or None: the newest
    ``.xplane.pb`` under ``.bench_trace/`` of the checkout that holds
    ``metric_file``, which the run has just written. Loaded once for all
    the metrics that read it."""
    if not run.get("trace"):
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(metric_file))))
    found = glob.glob(os.path.join(root, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    return _LOADED[key]


# ---------------------------------------------------------------------------
# host spans

def nest(spans) -> list:
    """Each span's parent: the innermost span on its line that holds it."""
    parent = [None] * len(spans)
    stack, line = [], None
    for i in sorted(range(len(spans)), key=lambda i: (
            spans[i].line, spans[i].start, -spans[i].end)):
        s = spans[i]
        if s.line != line:
            stack, line = [], s.line
        while stack and spans[stack[-1]].end < s.end:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def children(trace: Trace) -> list:
    kids = [[] for _ in trace.spans]
    for i, p in enumerate(trace.parent):
        if p is not None:
            kids[p].append(i)
    return kids


def depth(trace: Trace, i: int) -> int:
    d = 0
    while trace.parent[i] is not None:
        i, d = trace.parent[i], d + 1
    return d


def span_table(trace: Trace) -> dict:
    """{name: [count, self seconds]}: a span's time less its children's."""
    out = collections.defaultdict(lambda: [0, 0.0])
    kids = children(trace)
    for i, s in enumerate(trace.spans):
        own = (s.end - s.start) - sum(trace.spans[k].end - trace.spans[k].start
                                      for k in kids[i])
        out[s.name][0] += 1
        out[s.name][1] += own * 1e-9
    return dict(out)


def _median_ms(values):
    return statistics.median(values) * 1e-6 if values else None


def engine_host_ms(trace: Trace):
    """Median over ``engine.decode`` spans of the span less its
    ``engine.decode.wait``: host time a step takes to dispatch, fetch and
    commit its tokens."""
    kids = children(trace)
    sp = trace.spans
    return _median_ms([
        (s.end - s.start) - sum(sp[k].end - sp[k].start for k in kids[i]
                                if sp[k].name == "engine.decode.wait")
        for i, s in enumerate(sp) if s.name == "engine.decode"])


def frontend_host_ms(trace: Trace):
    """Median over the ``frontend.step`` spans that hold an
    ``engine.decode`` of the step less its ``sched.*`` and ``engine.*``
    children: expiry, refill, streaming to handles, retires."""
    kids = children(trace)
    sp = trace.spans
    out = []
    for i, s in enumerate(sp):
        if s.name != "frontend.step":
            continue
        engine = [k for k in kids[i] if sp[k].name.startswith(
            ("sched.", "engine."))]
        if any(sp[k].name == "engine.decode" for k in engine):
            out.append((s.end - s.start) - sum(sp[k].end - sp[k].start
                                               for k in engine))
    return _median_ms(out)


# ---------------------------------------------------------------------------
# host spans against device time

def _device_lines(events):
    by_plane = collections.defaultdict(lambda: {MODULES: [], OPS: []})
    for plane, line, name, start, dur in events:
        by_plane[plane][line].append((start, dur, name))
    return by_plane


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b) -> list:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_under_spans_s(trace: Trace, prefixes=SERVING):
    """Seconds in which no op ran on the device while the host was inside
    a span named with one of ``prefixes``, averaged over the device
    planes; None without such spans or device planes."""
    host = trace_reduce.union((s.start, s.end) for s in trace.spans
                              if s.name.startswith(prefixes))
    planes = _device_lines(trace.events)
    if not host or not planes:
        return None
    total = 0.0
    for lines in planes.values():
        busy = trace_reduce.union((s, s + d)
                                  for s, d, _ in lines[OPS] or lines[MODULES])
        total += _measure(host) - _measure(_intersect(host, busy))
    return total / len(planes) * 1e-9


def who(trace: Trace, start: float, end: float) -> str:
    """The innermost span that covers at least half of [start, end]."""
    best, best_depth = "none", -1
    for i, s in enumerate(trace.spans):
        cover = min(s.end, end) - max(s.start, start)
        if cover * 2 >= end - start and cover > 0:
            d = depth(trace, i)
            if d > best_depth:
                best, best_depth = s.name, d
    return best


def idle_gaps(trace: Trace, top: int = 10) -> list:
    """The ``top`` longest idle gaps as ``[label, seconds]``, labelled as
    ``trace_reduce.reduce`` labels them with the host span that covers
    most of the gap in front: ``engine.decode.wait (jit_a -> jit_b)``."""
    gaps = []
    for lines in _device_lines(trace.events).values():
        merged = trace_reduce.union(
            (s, s + d) for s, d, _ in lines[OPS] or lines[MODULES])
        starts = sorted((s, trace_reduce.program_name(n))
                        for s, _, n in lines[MODULES])
        ends = sorted((s + d, trace_reduce.program_name(n))
                      for s, d, n in lines[MODULES])
        for (_, e0), (e1, _) in zip(merged, merged[1:]):
            i = bisect.bisect_right(ends, (e0 + 1, "￿")) - 1
            j = bisect.bisect_left(starts, (e1 - 1, ""))
            gaps.append((e1 - e0, e0, e1,
                         ends[i][1] if i >= 0 else "?",
                         starts[j][1] if j < len(starts) else "?"))
    gaps.sort(key=lambda g: -g[0])
    return [[f"{who(trace, e0, e1)} ({a} -> {b})", dur * 1e-9]
            for dur, e0, e1, a, b in gaps[:top]]


def decode_on_clock(trace: Trace, program: str = "jit__decode_impl"):
    """(runs of ``program`` inside an ``engine.decode`` span that end
    before its ``engine.decode.wait`` ends, runs of ``program``): how far
    the host's and the device's clocks agree."""
    kids = children(trace)
    sp = trace.spans
    steps = sorted((s.start, s.end, max((sp[k].end for k in kids[i]
                                         if sp[k].name ==
                                         "engine.decode.wait"), default=None))
                   for i, s in enumerate(sp) if s.name == "engine.decode")
    runs = [(s, s + d) for _, line, n, s, d in trace.events
            if line == MODULES and trace_reduce.program_name(n) == program]
    inside = 0
    for s, e in runs:
        k = bisect.bisect_right(steps, (s, float("inf"), 0.0)) - 1
        if k >= 0 and e <= steps[k][1] and steps[k][2] is not None \
                and e <= steps[k][2]:
            inside += 1
    return inside, len(runs)


# ---------------------------------------------------------------------------
# device scopes

_HEAD = re.compile(r"(?:ROOT )?%?([\w.\-]+) = (.*?) [a-z][\w\-]*\(")


def op_head(text: str) -> str:
    """``name = shape`` of an HLO instruction, as an op event's name and a
    compiled module's text both begin: ``fusion.3 = f32[8]{0}``."""
    m = _HEAD.match(text.strip())
    return f"{m.group(1)} = {m.group(2)}" if m else text


def scope_map(hlo_text: str) -> dict:
    """{op head: name-stack path} from a compiled module's text
    (``compiled.as_text()``): ``jit(forward)/while/body/attn/add``."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and " = " in line:
            out[op_head(line)] = m.group(1)
    return out


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def forward_paths(run: dict, trace: Trace) -> dict:
    """The scope map of the classify cell's ``jit_forward``: the same
    forward, lowered again on the run's weights and first sampled batch
    and compiled (the compile cache has it), once per trace."""
    if trace.paths is None:
        import jax
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        model = build_model(ModelConfig(**run["model"]))

        def forward(p, x):
            return model.apply(p, {"images": x})

        trace.paths = scope_map(jax.jit(forward).lower(
            run["weights"], run["sample"][0]).compile().as_text())
    return trace.paths


def scope_ms(trace: Trace, scope: str, paths: dict,
             program: str = "jit_forward"):
    """Device milliseconds per run of ``program`` of the leaf ops whose
    path in ``paths`` (by ``op_head``) holds ``scope``, over the runs in
    the trace; None without a run, or without an op under the scope (a
    program with no scopes). A leaf op holds no other op: the loop that
    runs a scanned layer is not its layer's ops."""
    total, runs, seen = 0.0, 0, False
    for lines in _device_lines(trace.events).values():
        mods = sorted((s, s + d) for s, d, n in lines[MODULES]
                      if trace_reduce.program_name(n) == program)
        runs += len(mods)
        ops = sorted(lines[OPS], key=lambda o: (o[0], -o[1]))
        for k, (s, d, name) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][0] < s + d and \
                    ops[k + 1][0] + ops[k + 1][1] <= s + d:
                continue                       # holds the next op
            if not in_scope(paths.get(op_head(name), ""), scope):
                continue
            m = bisect.bisect_right(mods, (s, float("inf"))) - 1
            if m >= 0 and s + d <= mods[m][1]:
                total += d
                seen = True
    return total / runs * 1e-6 if runs and seen else None
