"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time per named program and per kernel, the
operations that took most time, and the longest idle gaps.

An event is a tuple ``(plane, line, name, start_ns, dur_ns)``. A device
plane is named ``/device:TPU:<n>``; on it the line ``XLA Modules`` holds one
event per run of a compiled program (``jit_<function>(<id>)``) and the line
``XLA Ops`` one event per operation, Pallas kernels among them under their
kernel function's name.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

MODULES, OPS = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str, planes_like: str = "/device:"):
    """The events of the planes whose name contains ``planes_like``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if planes_like not in plane.name:
            continue
        for line in plane.lines:
            if line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def op_name(text: str) -> str:
    """An op event's own name: ``%copy.172 = bf16[...] copy(...)`` ->
    ``copy.172``; its operands name other ops and are not its name."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """``jit__decode_impl(12)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events, kernels: dict, top: int = 10) -> dict:
    """Reduce the events of one traced window.

    ``kernels`` maps a kernel's name to a pattern in its op events' own
    names (the custom call carries the name of the function that wraps the
    Pallas call).
    Returns seconds throughout: ``busy_s`` (union of op intervals, averaged
    over the device planes), ``span_s`` (first op start to last op end,
    averaged likewise: device work the host's trace window did not frame
    still counts in the window), ``programs`` {name: (count, total_s)},
    ``kernels`` {name: (count, total_s)}, ``device_ops`` and ``idle_gaps``
    (the ``top`` largest, as [name, seconds])."""
    by_plane = collections.defaultdict(lambda: {MODULES: [], OPS: []})
    for plane, line, name, start, dur in events:
        by_plane[plane][line].append((start, dur, name))
    if not by_plane:
        return {"busy_s": 0.0, "span_s": 0.0, "programs": {},
                "kernels": {k: (0, 0.0) for k in kernels},
                "device_ops": [], "idle_gaps": [], "planes": 0}
    busy, span, gaps = 0.0, 0.0, []
    programs = collections.defaultdict(lambda: [0, 0.0])
    kern = {k: [0, 0.0] for k in kernels}
    ops = collections.Counter()
    for lines in by_plane.values():
        acts = lines[OPS] or lines[MODULES]
        merged = union((s, s + d) for s, d, _ in acts)
        busy += sum(e - s for s, e in merged) * 1e-9
        span += (merged[-1][1] - merged[0][0]) * 1e-9 if merged else 0.0
        for s, d, name in lines[MODULES]:
            p = programs[program_name(name)]
            p[0] += 1
            p[1] += d * 1e-9
        for s, d, name in lines[OPS]:
            ops[name[:200]] += d * 1e-9
            own = op_name(name)
            for k, pat in kernels.items():
                if pat in own:
                    kern[k][0] += 1
                    kern[k][1] += d * 1e-9
        starts = sorted((s, program_name(n)) for s, _, n in lines[MODULES])
        ends = sorted((s + d, program_name(n)) for s, d, n in lines[MODULES])
        for (_, e0), (e1, _) in zip(merged, merged[1:]):
            i = bisect.bisect_right(ends, (e0 + 1, "￿")) - 1
            j = bisect.bisect_left(starts, (e1 - 1, ""))
            label = f"{ends[i][1] if i >= 0 else '?'} -> " \
                    f"{starts[j][1] if j < len(starts) else '?'}"
            gaps.append([label, (e1 - e0) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy / len(by_plane), "span_s": span / len(by_plane),
            "programs": {k: tuple(v) for k, v in programs.items()},
            "kernels": {k: tuple(v) for k, v in kern.items()},
            "device_ops": [[n, t] for n, t in ops.most_common(top)],
            "idle_gaps": gaps[:top], "planes": len(by_plane)}


def program_time(red: dict, fn: str):
    """(count, total seconds) of the programs jitted from function ``fn``."""
    hits = [v for k, v in red["programs"].items()
            if k == f"jit_{fn}" or k.endswith(f"_{fn}") or k == fn]
    return (sum(c for c, _ in hits), sum(t for _, t in hits))
