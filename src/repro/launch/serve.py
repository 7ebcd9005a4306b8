"""Serving driver: continuous batching over a synthetic ragged arrival
trace, driven through the async front-end (bounded queue, deadlines,
prefix cache — reports per-status counts plus p50/p99 latency/ttft; see
docs/serving.md):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b-reduced \
        --trace 24 --slots 4 --max-len 128

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b-reduced \
        --trace 32 --slots 4 --rate 20 --queue-depth 8 \
        --deadline-ms 200,800 --deadline-frac 0.5 \
        --prefix-cache 8 --prefix-len 24

With --ckpt-in it serves a pruned checkpoint produced by repro.launch.prune
(pass --sparsity to match); pruned configs shrink the KV cache automatically.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import latest_step, restore_checkpoint
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import resolve_config
from repro.models import build_model


def serve_trace(model, params, *, n, slots, max_len, prompt_range, gen_range,
                rate=None, seed=0, queue_depth=16,
                deadline_ms=None, deadline_frac=1.0, prefix_cache=0,
                prefix_len=0, spf=False, replicas=1, route="least-loaded",
                mem_len=None, sharding=None, prefill_chunk=None, log=print):
    """Async front-end + continuous-batching engine over a synthetic trace.

    The trace drives the full serving stack: Poisson arrivals (``rate``),
    a deadline mix (``deadline_ms`` range hits ``deadline_frac`` of the
    requests), bounded-queue admission (``queue_depth``, FIFO or
    shortest-prompt-first), and optional prefix-cache reuse of a shared
    ``prefix_len``-token system prompt. Overload surfaces as typed
    rejections in the table, never as a deadlock.

    With ``replicas > 1`` the trace is served by a fleet of engines behind
    a :class:`~repro.serve.ReplicaRouter` (``route`` picks the policy);
    the front-end layers on the router exactly as it layers on one engine.
    Prefix caching in routed mode is per-replica and owned by the router
    (``route=prefix-affinity``); the front-end's shared cache is
    single-engine only.

    With a ``sharding`` (``repro.serve.ServeSharding``, built from
    ``--mesh-shape``/``--serve-sharded``) each engine's decode step runs
    under pjit with the slot cache model-sharded; the report additionally
    logs the per-device cache footprint (docs/serving.md "Mesh-sharded
    serving").

    With ``prefill_chunk`` set, cold admits prefill at most that many
    prompt tokens per engine iteration (docs/serving.md "Scheduler"):
    occupied slots take a decode step between chunks, so long prompts
    never freeze co-resident streams; token output is byte-identical.
    """
    from repro.serve import (PrefixCache, ReplicaRouter, ServeEngine,
                             ServeFrontend, frontend_table,
                             synthetic_trace)
    from repro.serve.engine import format_table
    cfg = model.cfg
    dl_range = None if deadline_ms is None else \
        tuple(x / 1e3 for x in deadline_ms)
    trace = synthetic_trace(n, cfg.vocab_size, seed=seed,
                            prompt_range=prompt_range, gen_range=gen_range,
                            rate=rate, deadline_range=dl_range,
                            deadline_frac=deadline_frac,
                            prefix_len=prefix_len, mem_len=mem_len,
                            d_model=cfg.d_model)
    engines = [ServeEngine(model, params, n_slots=slots, max_len=max_len,
                           mem_len=mem_len, sharding=sharding)
               for _ in range(max(1, replicas))]
    for e in engines:
        e.warmup(prompt_lens=[len(r.tokens) for r in trace],
                 prefix=prefix_cache > 0, prefill_chunk=prefill_chunk)
    if replicas > 1:
        eng = ReplicaRouter(engines, route=route, prefix_cap=prefix_cache)
        pc = None
    else:
        eng = engines[0]
        pc = PrefixCache(cap=prefix_cache) if prefix_cache > 0 else None
    fe = ServeFrontend(eng, queue_depth=queue_depth,
                       policy="spf" if spf else "fifo", prefix_cache=pc,
                       prefill_chunk=prefill_chunk)
    t0 = time.perf_counter()
    handles = fe.run(trace, log=log)
    wall = time.perf_counter() - t0
    table = frontend_table(handles, wall)
    table["mode"] = f"fleet-x{replicas}" if replicas > 1 else "frontend"
    log(f"[serve] frontend: {eng.stats['admits']} admits, "
        f"{eng.stats['decode_steps']} decode steps, "
        f"lane utilization "
        f"{eng.stats['decode_lanes'] / max(1, eng.stats['decode_steps'] * slots):.0%}, "
        f"cache {eng.cache_bytes / 1e6:.2f} MB")
    if sharding is not None:
        e0 = engines[0]
        log(f"[serve] sharded over {dict(sharding.sizes)}: per-device "
            f"cache {e0.device_cache_bytes / 1e6:.2f} MB "
            f"({e0.cache_bytes / max(e0.device_cache_bytes, 1):.2f}x "
            f"smaller than unsharded)")
    if replicas > 1:
        log(f"[serve] router: {dict(eng.rstats)}; "
            f"states {[s.value for s in eng.states]}")
        if eng.prefix_stats() is not None:
            for i, st in enumerate(eng.prefix_stats()):
                log(f"[serve] replica {i} prefix cache: {st}")
    if pc is not None:
        log(f"[serve] prefix cache: {pc.stats()}")
    keys = ["mode", "requests", "done", "rejected", "expired", "failed",
            "tokens", "tok_per_s", "lat_p50_ms", "lat_p99_ms",
            "ttft_p50_ms", "ttft_p99_ms"]
    log(format_table([table], keys))
    return handles, table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--expert-sparsity", type=float, default=0.0,
                    help="serve with a fraction of routed experts removed "
                         "(MoE archs; mirrors repro.launch.prune)")
    ap.add_argument("--mem-len", type=int, default=None,
                    help="enc-dec only: fixed encoder-memory length; trace "
                         "requests carry synthetic frames of this length")
    ap.add_argument("--ckpt-in", default=None)
    ap.add_argument("--trace", type=int, required=True,
                    help="serve N synthetic ragged requests through the "
                         "continuous-batching engine")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine slots (concurrent requests)")
    ap.add_argument("--max-len", type=int, default=128,
                    help="per-slot sequence budget (prompt + gen)")
    ap.add_argument("--prompt-range", default="8,48",
                    help="trace prompt lengths, 'lo,hi'")
    ap.add_argument("--gen-range", default="4,48",
                    help="trace generation lengths, 'lo,hi'")
    ap.add_argument("--rate", type=float, default=None,
                    help="trace arrival rate (req/s); default all at t=0")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="bounded admission queue beyond the slots; "
                         "requests past it are rejected (backpressure)")
    ap.add_argument("--deadline-ms", default=None,
                    help="per-request deadline budget, 'lo,hi' ms after "
                         "arrival; expired requests keep partial tokens")
    ap.add_argument("--deadline-frac", type=float, default=1.0,
                    help="fraction of requests given a deadline "
                         "(the deadline mix)")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="prefix-cache capacity in entries; 0 disables "
                         "(pure global-attention LMs only)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared system-prompt tokens prepended to every "
                         "trace request (the prefix-cache workload)")
    ap.add_argument("--spf", action="store_true",
                    help="shortest-prompt-first admission instead of FIFO")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max prompt tokens a cold admit prefills per "
                         "engine iteration (chunked prefill via the "
                         "scheduler); default: atomic whole-prompt admits")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the ReplicaRouter; 1 "
                         "serves through a single engine (no router)")
    ap.add_argument("--route", default="least-loaded",
                    choices=["least-loaded", "prefix-affinity"],
                    help="fleet routing policy: fewest occupied slots, or "
                         "longest cached prefix (per-replica caches; pure "
                         "global-attention LMs only)")
    ap.add_argument("--mesh-shape", default=None,
                    help="device mesh shape 'DxM' (data x model) for "
                         "--serve-sharded, e.g. 2x2; needs that many "
                         "devices (on CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4)")
    ap.add_argument("--serve-sharded", action="store_true",
                    help="run the shared decode step under pjit with the "
                         "slot cache model-sharded over --mesh-shape "
                         "(params placed by distrib.sharding.param_specs; "
                         "requires --mesh-shape)")
    args = ap.parse_args()
    if args.serve_sharded and not args.mesh_shape:
        ap.error("--serve-sharded requires --mesh-shape")

    use_compile_cache()
    sharding = None
    if args.serve_sharded:
        from repro.launch.mesh import make_mesh, parse_shape
        from repro.serve import ServeSharding
        sharding = ServeSharding(make_mesh(parse_shape(args.mesh_shape)))

    cfg = resolve_config(args.arch)
    if args.sparsity > 0 or args.expert_sparsity > 0:
        cfg = cfg.pruned(args.sparsity, args.sparsity,
                         expert_sparsity=args.expert_sparsity)
    model = build_model(cfg)
    if sharding is None:
        params = model.init(jax.random.PRNGKey(0))
    else:
        from repro.distrib.sharding import init_sharded
        params = init_sharded(model.init, jax.random.PRNGKey(0),
                              sharding.mesh)
    if args.ckpt_in:
        last = latest_step(args.ckpt_in)
        params, _ = restore_checkpoint(args.ckpt_in, last, params)
        print(f"[serve] loaded {args.ckpt_in} step {last}")
    pr = tuple(int(x) for x in args.prompt_range.split(","))
    gr = tuple(int(x) for x in args.gen_range.split(","))
    dl = None if args.deadline_ms is None else \
        tuple(float(x) for x in args.deadline_ms.split(","))
    serve_trace(model, params, n=args.trace, slots=args.slots,
                max_len=args.max_len, prompt_range=pr, gen_range=gr,
                rate=args.rate, seed=args.seed,
                queue_depth=args.queue_depth, deadline_ms=dl,
                deadline_frac=args.deadline_frac,
                prefix_cache=args.prefix_cache,
                prefix_len=args.prefix_len, spf=args.spf,
                replicas=args.replicas, route=args.route,
                mem_len=args.mem_len, sharding=sharding,
                prefill_chunk=args.prefill_chunk)


if __name__ == "__main__":
    main()
