"""Closed-loop batch classification with a ViT through the program's
``model.apply``, jitted.

Set-up makes the weights and a pool of image batches on the device from the
seed and compiles the forward at the traffic's batch. The window sends
batch after batch, cycling through the pool, with a fixed number queued on
the device, and keeps every batch's logits on the device.

``correct``: after the window, a seeded sample of the window's batches is
run through the plain float32 reference; the widest relative L2 error of
an image's logits must stay under the cell's limit. The attention must
have run its Pallas kernel.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

import harness

TRACE_S = 4.0           # traced: the last seconds of the window
SAMPLE_BATCHES = 2
# batches queued on the device ahead of the one the host waits for, so a
# host stall shorter than IN_FLIGHT batches leaves the device busy
IN_FLIGHT = 3
ON_PATH = ("flash_attention",)                  # must run its Pallas kernel


def run(cell, *, ref, dev, seed, seconds, trace, rate, allowed, trace_dir):
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    m, tr = cell.config["model"], cell.traffic
    B, pool = tr["batch"], tr["pool"]
    before = harness.resolutions()
    t0 = time.perf_counter()
    model = build_model(ModelConfig(**m))
    weights = ref.init(seed, m)
    shape = (pool, B, m["img_size"], m["img_size"], 3)
    images = jax.jit(lambda k: list(jax.random.normal(k, shape)))(
        jax.random.key((seed + 1) % (2 ** 32)))
    def forward(p, x):
        return model.apply(p, {"images": x})

    fwd = jax.jit(forward)
    jax.block_until_ready(fwd(weights, images[0]))
    setup_s = time.perf_counter() - t0
    harness.log(f"[classify] set-up {setup_s!r} s; batch {B}, pool {pool}")

    count = harness.CompileCount()
    outs = []
    trace_win = [None, None]
    count.on = True
    start = time.perf_counter()
    end = start + seconds
    trace_from = end - min(TRACE_S, seconds)
    while True:
        now = time.perf_counter()
        if trace and trace_win[0] is None and now >= trace_from:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            trace_win[0] = time.perf_counter()
        if now >= end:
            break
        outs.append(fwd(weights, images[len(outs) % pool]))
        if len(outs) > IN_FLIGHT:
            outs[-1 - IN_FLIGHT].block_until_ready()
    jax.block_until_ready(outs[-IN_FLIGHT:])
    stop = time.perf_counter()
    if trace:
        trace_win[1] = stop
        jax.profiler.stop_trace()
    count.on = False
    n = len(outs)
    rate_img = n * B / (stop - start)
    harness.log(f"[classify] {len(outs)} batches in {stop - start!r} s: "
                f"{rate_img!r} images/s; {count.n} programs built in the "
                f"window")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev["devices"])
    off, resolved = harness.kernel_check(allowed, ON_PATH, before)
    harness.log(f"[classify] kernels {resolved}")
    rng = np.random.default_rng([seed, 1])
    pick = sorted(rng.choice(len(outs), min(SAMPLE_BATCHES, len(outs)),
                             replace=False).tolist())
    got = {i: np.asarray(outs[i], np.float64) for i in pick}
    del outs, fwd, model
    err = max(rel_err(got[i], ref.logits(weights, images[i % pool],
                                         tuple(sorted(m.items()))))
              for i in pick)
    harness.log(f"[classify] reference over batches {pick}: widest "
                f"relative logit error {err!r}")
    red = None
    if trace:
        import trace_reduce
        red = trace_reduce.reduce(
            trace_reduce.load_events(trace_reduce.find_xplane(trace_dir)),
            {"flash_attention": "flash_attention"})
    return {
        "attempted": n * B, "failed": 0, "setup_s": setup_s,
        "memory_peak_bytes": peak, "images_per_s": rate_img,
        "model": m, "peaks": dev["peaks"], "batch": B,
        # forwards that ran on the device in the traced window, batches
        # queued before it began included
        "traced_batches": trace_reduce.program_time(red, "forward")[0]
        if trace else None, "trace": red,
        "trace_window_s": max(trace_win[1] - trace_win[0],
                              red["span_s"]) if trace else None,
        "compiles_in_window": count.n,
        "weights": weights, "sample": [images[i % pool] for i in pick],
        "checks": {
            "logit_rel_err": {"value": err,
                              "limit": cell.limits["logit_rel_err"]},
            "off_pallas": {"value": off, "limit": 0}},
    }


def control(res, ref, m) -> float:
    """The control's reading on a run's sample: the widest relative logit
    error of the bfloat16 reference against the float32 one."""
    items = tuple(sorted(m.items()))
    return max(rel_err(np.asarray(ref.logits(res["weights"], x, items,
                                             "bfloat16"), np.float64),
                       ref.logits(res["weights"], x, items))
               for x in res["sample"])


def rel_err(got, want) -> float:
    """Widest per-row relative L2 error of ``got`` against ``want``."""
    want = np.asarray(want, np.float64)
    num = np.linalg.norm(got - want, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(want, axis=-1),
                                         1e-30)))
