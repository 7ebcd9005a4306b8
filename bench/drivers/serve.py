"""Open-loop serving of a language model through the program's front-end,
scheduler, engine and slot cache.

Requests come due on the traffic's schedule and are handed to
``ServeFrontend.submit`` when due; the loop steps the front-end between
arrivals. Every time is taken from when its request was due, so a stall of
the loop or of the server shows in the latency of every request it delays.
After the window no request is admitted; the sample (every request due in
the window) drains for at most ``DRAIN_S`` seconds, and what is left counts
as failed.

``correct``: once the window has closed and the program's state is freed,
a sample of finished requests, drawn from the seed and holding the longest,
is run through the plain float32 reference over its prompt and served
tokens; the widest gap by which a served token's logit lies below the
reference's best must stay under the cell's limit. Every attention op on
the path must have run its Pallas kernel.
"""
from __future__ import annotations

import gc
import shutil
import time

import numpy as np

import harness
import loadgen

DRAIN_S = 120.0         # after the window, before an unfinished one fails
TRACE_S = 6.0           # traced: the last seconds of the arrival window
SAMPLE_TOKENS = 512     # served tokens checked, besides the longest request
SAMPLE_MAX = 8
ON_PATH = ("flash_attention", "flash_decode")   # must run their Pallas kernels


def run(cell, *, ref, dev, seed, seconds, trace, rate, allowed, trace_dir):
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.serve import ServeEngine, ServeFrontend
    from repro.serve.engine import Request
    from repro.serve.queue import Status

    m, tr, sv = cell.config["model"], cell.traffic, cell.traffic["serve"]
    before = harness.resolutions()
    t0 = time.perf_counter()
    weights = jax.block_until_ready(ref.init(seed, m))
    engine = ServeEngine(build_model(ModelConfig(**m)), weights,
                         n_slots=sv["slots"], max_len=sv["max_len"])
    reqs = loadgen.open_loop(tr, seed, seconds, m["vocab_size"], rate)
    engine.warmup(prompt_lens=loadgen.lengths_used(tr, seconds, rate),
                  gen=2, prefill_chunk=sv["prefill_chunk"])
    fe = ServeFrontend(engine, queue_depth=sv["queue_depth"],
                       prefill_chunk=sv["prefill_chunk"])
    setup_s = time.perf_counter() - t0
    harness.log(f"[serve] set-up {setup_s!r} s; {len(reqs)} requests due "
                f"in {seconds} s")

    # the benchmark's own spans around the calls into the engine
    admit_t, prefills, decodes = {}, [], []
    clock = time.perf_counter
    begin_admit, decode_step = engine.begin_admit, engine.decode_step
    tracing = [False]

    def timed_admit(req, slot, prefix_cache=None):
        admit_t[req.rid] = clock()
        if tracing[0]:
            prefills.append(len(req.tokens))
        return begin_admit(req, slot, prefix_cache=prefix_cache)

    def timed_decode():
        if tracing[0]:
            ctx = [len(s.req.tokens) + len(s.out) for s in engine.slots
                   if not s.free and s.pending is None]
            decodes.append((len(ctx), sum(ctx)))
        return decode_step()

    engine.begin_admit, engine.decode_step = timed_admit, timed_decode

    count = harness.CompileCount()
    times = {r.rid: [] for r in reqs}
    handles, submit_t, live = {}, {}, set()

    def observe():
        now = clock()
        for rid in list(live):
            h = handles[rid]
            new = len(h.tokens) - len(times[rid])
            times[rid].extend([now] * max(new, 0))
            if h.finished:
                live.discard(rid)

    start = clock()
    due = [start + r.due for r in reqs]
    end_window = start + seconds
    trace_from = end_window - min(TRACE_S, seconds)
    trace_win = [None, None]
    count.on = True
    i = 0
    while True:
        now = clock()
        if trace and trace_win[0] is None and now >= trace_from:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            trace_win[0], tracing[0] = clock(), True
        if trace_win[0] is not None and trace_win[1] is None and (
                now >= end_window or (i == len(reqs) and not live)):
            tracing[0] = False
            trace_win[1] = clock()
            jax.profiler.stop_trace()
        while i < len(reqs) and due[i] <= now:
            r = reqs[i]
            submit_t[r.rid] = clock()
            handles[r.rid] = fe.submit(Request(rid=r.rid, tokens=r.prompt,
                                               gen=r.gen, arrival=r.due))
            live.add(r.rid)
            i += 1
            observe()
        if i == len(reqs) and not live and (not trace or trace_win[1]):
            break
        if clock() > end_window + DRAIN_S:
            break
        busy = fe.step()
        observe()
        if not busy and i < len(reqs):
            time.sleep(max(0.0, min(due[i] - clock(), 1e-3)))
    stop = clock()
    count.on = False

    # latencies from due times; a request that never gave a token counts
    # with the time it waited until the loop ended
    ttft, itl, late, qwait, failed = [], [], [], [], 0
    for r, d in zip(reqs, due):
        h, ts = handles.get(r.rid), times[r.rid]
        done = h is not None and h.status is Status.DONE
        failed += not done
        ttft.append((ts[0] if ts else stop) - d)
        itl.extend(np.diff(ts).tolist())
        if r.rid in submit_t:
            late.append(submit_t[r.rid] - d)
        if r.rid in admit_t:
            qwait.append(admit_t[r.rid] - d)
    half = len(ttft) // 2
    harness.log(f"[serve] ttft p95 of the first and second half of the "
                f"window (a growing backlog reads higher in the second): "
                f"{loadgen.percentile(ttft[:half] or [0], 95) * 1e3!r} ms, "
                f"{loadgen.percentile(ttft[half:], 95) * 1e3!r} ms")
    harness.log(f"[serve] {len(reqs)} due, {len(reqs) - failed} done, "
                f"{len(itl)} token gaps; ttft p50 "
                f"{loadgen.percentile(ttft, 50) * 1e3!r} ms, itl p50 "
                f"{loadgen.percentile(itl, 50) * 1e3!r} ms; "
                f"{count.n} programs built in the window")
    tail = sorted(range(len(reqs)), key=lambda j: -ttft[j])[:8]
    harness.log("[serve] slowest first tokens (due s, prompt, ttft ms, "
                "late ms, queue wait ms): " + "; ".join(
                    f"{reqs[j].due:.2f} {len(reqs[j].prompt)} "
                    f"{ttft[j] * 1e3:.1f} "
                    f"{(submit_t.get(reqs[j].rid, stop) - due[j]) * 1e3:.1f} "
                    f"{(admit_t.get(reqs[j].rid, stop) - due[j]) * 1e3:.1f}"
                    for j in tail))

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev["devices"])
    stats = dict(engine.stats)
    off, resolved = harness.kernel_check(allowed, ON_PATH, before)
    harness.log(f"[serve] kernels {resolved}")
    done = [r for r in reqs
            if r.rid in handles and handles[r.rid].status is Status.DONE]
    served = {r.rid: np.asarray(handles[r.rid].tokens, np.int32)
              for r in done}
    # free the program's state (the engine's jitted steps hold the engine
    # in a cycle) before the reference runs
    del engine.begin_admit, engine.decode_step
    del engine, fe, handles, begin_admit, decode_step, timed_admit, \
        timed_decode
    gc.collect()

    gap, checked = check(ref, weights, m, done, served, seed)
    harness.log(f"[serve] reference over {len(checked)} requests, "
                f"{sum(len(served[r]) for r in checked)} served tokens: "
                f"widest logit gap {gap!r}")
    red = None
    if trace:
        import trace_reduce
        red = trace_reduce.reduce(
            trace_reduce.load_events(trace_reduce.find_xplane(trace_dir)),
            {"flash_decode": "decode_attention_splits",
             "flash_attention": "flash_attention"})
    return {
        "attempted": len(reqs), "failed": failed, "setup_s": setup_s,
        "memory_peak_bytes": peak,
        "ttft_s": ttft, "itl_s": itl, "late_s": late, "queue_wait_s": qwait,
        "stats": stats, "model": m, "peaks": dev["peaks"],
        "prefills": prefills, "decodes": decodes, "trace": red,
        "trace_window_s": max(trace_win[1] - trace_win[0],
                              red["span_s"]) if trace else None,
        "compiles_in_window": count.n,
        "weights": weights,
        "sample": [(prompts_of(done)[rid], served[rid]) for rid in checked],
        "checks": {
            "logit_gap": {"value": gap,
                          "limit": cell.limits["logit_gap"]},
            "off_pallas": {"value": off, "limit": 0}},
    }


def prompts_of(done):
    return {r.rid: r.prompt for r in done}


def control(res, ref, m) -> float:
    """The control's reading on a run's sample: the widest reference gap of
    the tokens that the float8 reference puts first, at every position of
    the same prompts and served tokens."""
    return max(float(np.max(ref.gaps(res["weights"], m, p, s, fp8=True)))
               for p, s in res["sample"])


def check(ref, weights, m, done, served, seed):
    """Widest reference logit gap over a seeded sample of finished
    requests that holds the longest one."""
    if not done:
        return float("inf"), []
    longest = max(done, key=lambda r: (len(r.prompt) + r.gen, r.rid))
    rng = np.random.default_rng([seed, 1])
    pick, tokens = [longest.rid], 0
    for j in rng.permutation(len(done)):
        r = done[j]
        if tokens >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        if r.rid != longest.rid:
            pick.append(r.rid)
            tokens += r.gen
    prompts = prompts_of(done)
    gap = max(float(np.max(ref.gaps(weights, m, prompts[rid], served[rid])))
              for rid in pick)
    return gap, pick
