"""Open-loop serving of a mixture-of-experts decoder: the run of
``serve.py``, with the held experts' grouped matmul on the kernel check,
and, traced, in the trace's kernels.

The traffic and every time are ``serve.py``'s. Besides its
``flash_attention`` and ``flash_decode``, ``expert_gmm`` must have run its
Pallas kernel.

``correct`` compares the same sample with the same plain reference (each
request's reading made once), but ``logit_gap`` here is the mean, over
every served position of the sample, of how far the served token's logit
lies below the reference's best (``serve.py`` takes the widest). Under
bfloat16 about 3% of the served positions are not the reference's best,
and the widest gap of a few thousand positions reads about as high as
the float8 control's, whose wrong tokens also rank near the top (on one
v5e, 8 seeds: program 0.139-0.521, control 0.565-1.122). The mean counts
every position: a fault or a lower precision that moves the logits of
many moves it (the control reads 11-23 times the program on a seed). The
log line counts the positions whose token is not the reference's best,
and those of them where the reference's own routing sits at a near-tie
that a bfloat16 rounding of the router's input tips.

Traced, the trace ``serve.py`` left is reduced again with the latent
decode kernel and the expert kernel among the kernels, and
``decode_kernel_s`` holds each kernel's device time inside the decode
programs alone (the prefills run the expert kernel too), with the number
of decode programs whose events of it the trace kept; ``expert_weights``
there is the time of the ops that hand the expert kernel each layer's
held expert weights (the compiler slices them out of the stacked
weights into a buffer of their own before the kernel reads them).
"""
from __future__ import annotations

import bisect
import collections
import os

import numpy as np

import harness

serve = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve.py"))

ON_PATH = ("expert_gmm",)
KERNELS = {"flash_decode": "decode_attention_splits",
           "flash_attention": "flash_attention",
           "flash_decode_mla": "decode_latent_attention",
           "expert_gmm": "gmm"}
DECODE = "jit__decode_impl"


class _Once:
    """The plain reference, each sample request's reading made once:
    ``serve.run`` takes the widest gap, ``run`` here the mean, of the
    same arrays."""

    def __init__(self, ref):
        self.ref, self.seen = ref, {}

    def __getattr__(self, name):
        return getattr(self.ref, name)

    def readings(self, w, m, prompt, served, fp8=False):
        k = (np.asarray(prompt, np.int32).tobytes(),
             np.asarray(served, np.int32).tobytes(), fp8)
        if k not in self.seen:
            self.seen[k] = self.ref.readings(w, m, prompt, served, fp8)
        return self.seen[k]

    def gaps(self, w, m, prompt, served, fp8=False):
        return self.readings(w, m, prompt, served, fp8)[0]


def run(cell, *, ref, dev, seed, seconds, trace, rate, allowed, trace_dir):
    before = harness.resolutions()
    ref = _Once(ref)
    res = serve.run(cell, ref=ref, dev=dev, seed=seed, seconds=seconds,
                    trace=trace, rate=rate, allowed=allowed,
                    trace_dir=trace_dir)
    off, resolved = harness.kernel_check(allowed, ON_PATH, before)
    harness.log(f"[serve_moe] {resolved}")
    res["checks"]["off_pallas"]["value"] += off
    m = cell.config["model"]
    gaps, ties = sample_readings(res, ref, m)
    res["checks"]["logit_gap"]["value"] = float(gaps.mean()) \
        if gaps.size else float("inf")
    missed = gaps > 0
    harness.log(f"[serve_moe] {gaps.size} served positions checked: mean "
                f"logit gap {res['checks']['logit_gap']['value']!r}, "
                f"widest {float(gaps.max()) if gaps.size else None!r}, "
                f"not the reference's best at {int(missed.sum())} of them; "
                f"a routing near-tie (bf16 rounding of the router's input "
                f"changes a top-k) at {int(np.sum(ties > 0))} positions, "
                f"{int(np.sum(missed & (ties > 0)))} of those not the best")
    if trace:
        import trace_reduce
        events = trace_reduce.load_events(
            trace_reduce.find_xplane(trace_dir))
        res["trace"] = trace_reduce.reduce(events, KERNELS)
        res["decode_kernel_s"] = in_program(events, DECODE, KERNELS)
        res["decode_kernel_s"]["expert_weights"] = produced(
            events, DECODE, expert_weight_shapes(m))
        harness.log(
            f"[serve_moe] {res['trace']['planes']} device planes; kernels "
            f"(count, s) {res['trace']['kernels']}; decode programs "
            f"(count, s) "
            f"{trace_reduce.program_time(res['trace'], '_decode_impl')}, "
            f"in them (s, programs) {res['decode_kernel_s']}")
    return res


def sample_readings(res, ref, m, fp8=False) -> tuple:
    """(gaps, near-ties): every served position's reference logit gap over
    the run's sample (with ``fp8``, of the tokens the float8 control puts
    first), and the MoE layers whose routing a bfloat16 rounding of the
    router's input changes there (``readings`` of the reference)."""
    got = [ref.readings(res["weights"], m, p, s, fp8)
           for p, s in res["sample"]]
    if not got:
        return np.zeros(0), np.zeros(0, np.int32)
    return (np.concatenate([np.asarray(g, np.float64) for g, _ in got]),
            np.concatenate([np.asarray(t) for _, t in got]))


def control(res, ref, m) -> float:
    """The control's reading on a run's sample: the mean reference gap of
    the tokens the float8 reference puts first."""
    gaps, _ = sample_readings(res, ref, m, fp8=True)
    harness.log(f"[serve_moe] control: float8's first token not the "
                f"reference's best at {int(np.sum(gaps > 0))} of "
                f"{gaps.size} positions")
    return float(gaps.mean())


def expert_weight_shapes(m: dict) -> tuple:
    """HLO shapes of one layer's held expert weights: (E, D, F) of the
    gate and up projections, (E, F, D) of the down projection."""
    dt = {"bfloat16": "bf16", "float32": "f32"}[m["dtype"]]
    E = m["moe"].get("held") or m["moe"]["num_experts"]
    D, F = m["d_model"], m["d_ff_kept"]
    return (f"{dt}[{E},{D},{F}]", f"{dt}[{E},{F},{D}]")


def produced(events, program: str, shapes: tuple) -> tuple:
    """(seconds, runs) of the ops inside runs of ``program`` whose result
    has one of ``shapes``."""
    spent, held = 0.0, set()
    for plane, i, name, sec in _inside(events, program):
        result = name.split(" = ", 1)[-1]
        if any(result.startswith(sh + "{") for sh in shapes):
            spent += sec
            held.add((plane, i))
    return spent, len(held)


def _inside(events, program: str):
    """(plane, run index, name, seconds) of each op event that starts
    inside a run of ``program`` on its device plane."""
    import trace_reduce
    runs = collections.defaultdict(list)
    for plane, line, name, start, dur in events:
        if line == trace_reduce.MODULES and \
                trace_reduce.program_name(name) == program:
            runs[plane].append((start, start + dur))
    for r in runs.values():
        r.sort()
    for plane, line, name, start, dur in events:
        r = runs[plane]
        i = bisect.bisect_right(r, (start, float("inf"))) - 1
        if line == trace_reduce.OPS and i >= 0 and start < r[i][1]:
            yield plane, i, name, dur * 1e-9


def in_program(events, program: str, kernels: dict) -> dict:
    """{kernel: (seconds, runs)}: the seconds of each kernel's op events
    inside runs of ``program``, summed over the device planes (as
    ``trace_reduce.reduce`` sums a kernel's time), and how many runs hold
    one. A run whose op events the trace did not keep counts neither."""
    import trace_reduce
    spent = dict.fromkeys(kernels, 0.0)
    held = {k: set() for k in kernels}
    for plane, i, name, sec in _inside(events, program):
        own = trace_reduce.op_name(name)
        for k, pat in kernels.items():
            if pat in own:
                spent[k] += sec
                held[k].add((plane, i))
    return {k: (spent[k], len(held[k])) for k in kernels}
