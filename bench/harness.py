"""What every cell shares: finding a cell's files by name, the look for the
chip, the compile cache and compile count, the check that kernels ran, and
the metric readers.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files:

* ``configs[*].file``: the model sizes as run, and which plain reference
  in ``bench/reference/`` computes it;
* ``bench/traffic/<traffic>.json``: the traffic parameters, and which
  driver in ``bench/drivers/`` runs it;
* ``bench/limits/<workload>.json``: the limit of each number ``correct``
  compares;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``,
  returning a number or None when the run has nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import types

import peaks as peaks_mod

def load_module(path: str) -> types.ModuleType:
    name = "bench_" + os.path.relpath(path).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell(types.SimpleNamespace):
    """One workload with everything found by its names."""


def find_cell(root: str, name: str) -> Cell:
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"({sorted(work)})")
    w = work[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = read_json(os.path.join(root, conf["file"]))
    bench = os.path.join(root, "bench")
    traffic = read_json(os.path.join(bench, "traffic",
                                     w["traffic"] + ".json"))
    limits = read_json(os.path.join(bench, "limits", name + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    return Cell(root=root, bench=bench, workload=w, config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=layer)


def device_check(chips: int) -> dict:
    """Platform, kind and count of the devices JAX finds; exits non-zero
    before any cell without a TPU in the peaks table, or with fewer chips
    than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    try:
        pk = peaks_mod.peaks_for(d.platform, d.device_kind)
    except peaks_mod.UnknownDevice as e:
        raise SystemExit(f"bench: {e}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, jax found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peaks": pk, "devices": devs[:chips]}


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is cached, so
    only a checkout's first run of a cell compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCount:
    """Counts programs built (compiled or loaded from the cache) while
    ``on``; the measured window should build none."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0

        def listen(event, duration, **kw):
            if self.on and event.endswith("backend_compile_duration"):
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def resolutions():
    """A copy of the program's record of which implementation each kernel
    dispatcher traced to, to tell a run's own resolutions from earlier
    ones in the same process."""
    from repro import kernels
    return kernels.RESOLVED.copy()


def kernel_check(allowed: set, ops: tuple, before) -> tuple:
    """(count of off-path resolutions of ``ops`` since ``before``, text)."""
    took = {f"{op}:{impl}": n
            for (op, impl), n in (resolutions() - before).items()}
    off = [k for k in took if k.split(":")[0] in ops
           and k.split(":")[1] not in allowed]
    missing = [op for op in ops if not any(
        k.startswith(op + ":") for k in took)]
    return len(off) + len(missing), f"resolved {took}"


def read_metrics(bench: str, metrics: list, run: dict) -> dict:
    out = {}
    for m in metrics:
        v = load_module(os.path.join(bench, "metrics",
                                     m["name"] + ".py")).read(run)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def log(*a):
    print(*a, file=sys.stderr, flush=True)
