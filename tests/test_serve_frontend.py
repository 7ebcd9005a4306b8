"""Front-end over the *real* engine: deadline edge cases, streaming,
prefix-cache exactness, asyncio interleaving, and byte identity with the
engine's own trace runner.

Deadline decisions all flow through the front-end's injectable clock, so a
manual clock makes every expiry boundary deterministic even with a real
jitted model underneath. The four edge cases ISSUE 6 names each get a
test: expiry exactly at the admit boundary, during prefill, at the final
decode step, and while queued — each asserting the partial-token count
and that the freed slot is refilled.
"""
from __future__ import annotations

import asyncio
import re

import jax
import numpy as np
import pytest

from helpers import tiny_cfg
from repro.models import build_model
from repro.serve import (AsyncServeFrontend, Overloaded, PrefixCache,
                         ServeEngine, ServeFrontend, Status, errors,
                         frontend_table, synthetic_trace)
from repro.serve.engine import Request
from repro.serve.testing import FleetFakeEngine


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg("qwen2-1.5b")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(lm, n_slots=2, max_len=48):
    model, params = lm
    return ServeEngine(model, params, n_slots=n_slots, max_len=max_len)


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _req(rid, plen, gen, deadline=None):
    return Request(rid=rid, tokens=(np.arange(plen) % 7 + 1 + rid)
                   .astype(np.int32), gen=gen, deadline=deadline)


def _prefills(eng):
    return sum(v for k, v in eng.stats.items() if k.startswith("prefill"))


# ---------------------------------------------------------------------------
# byte identity with the engine's own runner (acceptance criterion c)
# ---------------------------------------------------------------------------

def test_frontend_matches_engine_run_byte_identical(lm):
    """No deadlines, no prefix cache: the front-end's token streams must be
    byte-identical to ``ServeEngine.run`` on the same trace."""
    model, params = lm
    trace = synthetic_trace(n=6, seed=3, rate=50.0, prompt_range=(4, 10),
                            gen_range=(2, 6), vocab=model.cfg.vocab_size)
    eng_a = ServeEngine(model, params, n_slots=2, max_len=48)
    done = eng_a.run(trace)
    eng_b = ServeEngine(model, params, n_slots=2, max_len=48)
    handles = ServeFrontend(eng_b, queue_depth=8).run(trace)
    assert all(h.status is Status.DONE for h in handles)
    for h in handles:
        assert h.tokens == [int(t) for t in done[h.rid].tokens], \
            f"rid {h.rid}: stream diverged from engine.run"


# ---------------------------------------------------------------------------
# deadline edge cases (manual clock, real engine)
# ---------------------------------------------------------------------------

def test_deadline_expired_exactly_at_admit(lm):
    """deadline == clock at the admit boundary: expired *before* prefill —
    zero tokens, zero engine work, slot still admits the next request."""
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk)
    h = fe.submit(_req(0, 4, 5, deadline=0.0))    # dead on arrival
    assert h.status is Status.EXPIRED and h.tokens == []
    assert _prefills(eng) == 0 and eng.active_count() == 0
    g = fe.submit(_req(1, 4, 2))                  # slot was never consumed
    while not g.finished:
        fe.step()
    assert g.status is Status.DONE and len(g.tokens) == 2


def test_deadline_expired_during_prefill(lm):
    """Deadline passes while prefill runs: the prefill token is kept, the
    request expires with exactly 1 token, and the slot is refilled."""
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk)
    real_admit = eng.admit

    def slow_admit(req, slot, prefix_cache=None):
        real_admit(req, slot, prefix_cache=prefix_cache)
        clk.advance(10.0)                         # prefill "took" 10s

    eng.admit = slow_admit
    h = fe.submit(_req(0, 4, 6, deadline=5.0))
    assert h.status is Status.EXPIRED
    assert len(h.tokens) == 1                     # the prefill token only
    assert eng.stats["cancels"] == 1
    assert eng.active_count() == 0                # slot freed mid-flight
    g = fe.submit(_req(1, 4, 2))
    while not g.finished:
        fe.step()
    assert g.status is Status.DONE


def test_deadline_at_final_decode_step_completion_wins(lm):
    """Tie-break: a deadline passing *during* the final decode step loses
    to completion (the tokens exist); one step earlier it expires with
    partial tokens."""
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk)
    real_decode = eng.decode_step

    def timed_decode():
        out = real_decode()
        clk.advance(1.0)                          # each decode step = 1s
        return out

    eng.decode_step = timed_decode
    # gen=3: prefill tok@t=0, decode steps end at t=1 (tok2) and t=2 (tok3)
    h = fe.submit(_req(0, 4, 3, deadline=1.5))    # passes mid-final-step
    fe.step()                                     # tok2, clock -> 1.0
    fe.step()                                     # starts at 1.0 < 1.5: runs
    assert h.status is Status.DONE and len(h.tokens) == 3
    # sibling one step earlier: deadline passes before the final step starts
    g = fe.submit(_req(1, 4, 3, deadline=clk.t + 0.5))
    fe.step()                                     # tok2, clock passes dl
    fe.step()                                     # expiry check fires first
    assert g.status is Status.EXPIRED and len(g.tokens) == 2
    assert eng.active_count() == 0


def test_deadline_expired_while_queued(lm):
    """Queued expiry never touches the engine: no prefill for the dead
    request, survivors keep their order, slot refilled."""
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk)
    a = fe.submit(_req(0, 4, 6))                  # occupies the slot
    b = fe.submit(_req(1, 4, 3, deadline=2.0))    # waits, will die waiting
    c = fe.submit(_req(2, 4, 2))                  # waits behind b
    prefills_before = _prefills(eng)
    clk.advance(5.0)
    fe.step()
    assert b.status is Status.EXPIRED and b.tokens == []
    while not (a.finished and c.finished):
        fe.step()
    assert a.status is Status.DONE and len(a.tokens) == 6
    assert c.status is Status.DONE and len(c.tokens) == 2
    assert _prefills(eng) == prefills_before + 1  # c only, never b


# ---------------------------------------------------------------------------
# backpressure, cancel, streaming
# ---------------------------------------------------------------------------

def test_overload_rejects_with_typed_result(lm):
    eng = _engine(lm, n_slots=1)
    fe = ServeFrontend(eng, queue_depth=1, clock=ManualClock())
    hs = [fe.submit(_req(i, 4, 3)) for i in range(4)]
    rejected = [h for h in hs if h.status is Status.REJECTED]
    assert len(rejected) == 2                     # 1 slot + 1 queue seat
    for h in rejected:
        assert isinstance(h.result, Overloaded)
        assert h.result.queue_depth == 1 and "queue full" in str(h.result)
        assert h.tokens == []
    while fe.step():
        pass
    assert sum(h.status is Status.DONE for h in hs) == 2


def test_cancel_queued_and_running(lm):
    eng = _engine(lm, n_slots=1)
    fe = ServeFrontend(eng, queue_depth=4, clock=ManualClock())
    a = fe.submit(_req(0, 4, 8))
    b = fe.submit(_req(1, 4, 3))
    assert fe.cancel(1) and b.status is Status.CANCELLED and b.tokens == []
    fe.step()
    assert fe.cancel(0) and a.status is Status.CANCELLED
    assert 1 <= len(a.tokens) < 8                 # partials kept
    assert eng.active_count() == 0
    assert not fe.cancel(0)                       # already finished
    assert not fe.cancel(99)                      # unknown rid


def test_gen1_completes_at_admit(lm):
    eng = _engine(lm, n_slots=1)
    fe = ServeFrontend(eng, queue_depth=4, clock=ManualClock())
    h = fe.submit(_req(0, 4, 1))
    assert h.status is Status.DONE and len(h.tokens) == 1
    assert eng.active_count() == 0


def test_stream_yields_before_completion(lm):
    eng = _engine(lm, n_slots=1)
    fe = ServeFrontend(eng, queue_depth=4, clock=ManualClock())
    h = fe.submit(_req(0, 4, 5))
    it = fe.stream(h)
    first = next(it)
    assert not h.finished                         # token before completion
    rest = list(it)
    assert h.status is Status.DONE
    assert [first] + rest == h.tokens and len(h.tokens) == 5


def test_async_streams_interleave(lm):
    eng = _engine(lm, n_slots=2)
    afe = AsyncServeFrontend(ServeFrontend(eng, queue_depth=4))
    order = []

    async def consume(req, tag):
        h = await afe.submit(req)
        toks = []
        async for t in afe.stream(h):
            order.append(tag)
            toks.append(t)
        return toks

    async def main():
        return await asyncio.gather(
            consume(_req(0, 4, 4), "A"), consume(_req(1, 5, 4), "B"))

    ta, tb = asyncio.run(main())
    assert len(ta) == 4 and len(tb) == 4
    # genuinely interleaved: B streams a token before A's stream ends
    last_a = len(order) - 1 - order[::-1].index("A")
    assert order.index("B") < last_a, order


def test_async_driver_task_terminates_when_idle():
    """Regression: the driver task must end (not leak) once every handle
    is terminal and the queue is empty — and restart on a later submit.
    Pure-Python fake engine + injectable clock keep it deterministic."""
    fe = ServeFrontend(FleetFakeEngine(2), queue_depth=4,
                       clock=ManualClock())
    afe = AsyncServeFrontend(fe)

    def req(rid, gen):
        return Request(rid=rid, tokens=np.arange(1, 4, dtype=np.int32),
                       gen=gen)

    async def main():
        h0 = await afe.submit(req(0, 3))
        h1 = await afe.submit(req(1, 2))
        assert len([t async for t in afe.stream(h0)]) == 3
        for _ in range(8):                  # let the driver observe idle
            await asyncio.sleep(0)
        assert h0.finished and h1.finished
        assert afe._task is not None and afe._task.done(), \
            "driver task leaked after all handles terminal + queue empty"
        h2 = await afe.submit(req(2, 2))    # restarts the driver
        assert not afe._task.done()
        assert len([t async for t in afe.stream(h2)]) == 2
        for _ in range(8):
            await afe._asyncio.sleep(0)
        assert afe._task.done()

    asyncio.run(main())


def test_async_driver_terminates_despite_stranded_handle():
    """The exact leak the fix pins: the old exit condition also required
    *every known handle* to be finished, so an unfinished handle stranded
    outside queue/slots kept the driver spinning forever. `not busy`
    alone must end the task."""
    fe = ServeFrontend(FleetFakeEngine(1), queue_depth=4,
                       clock=ManualClock())
    afe = AsyncServeFrontend(fe)

    async def main():
        h = await afe.submit(Request(
            rid=0, tokens=np.arange(1, 4, dtype=np.int32), gen=5))
        # strand it: free the slot behind the front-end's back, so the
        # handle can never reach a terminal state
        slot = next(iter(fe._by_slot))
        fe._by_slot.pop(slot)
        s = fe.engine.slots[slot]
        s.rid, s.req, s.remaining = -1, None, 0
        for _ in range(8):
            await asyncio.sleep(0)
        assert not h.finished
        assert afe._task.done(), "driver spun forever on stranded handle"

    asyncio.run(main())


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_exact_and_counted(lm):
    """Requests sharing a 16-token prefix: cached serving produces the
    exact same tokens as cold serving, and the cache counts the hits."""
    model, params = lm
    shared = (np.arange(16) % 5 + 1).astype(np.int32)
    reqs = [Request(rid=i, tokens=np.concatenate(
                [shared, np.full((2,), 10 + i, np.int32)]), gen=4)
            for i in range(4)]

    def serve(prefix_cache):
        eng = ServeEngine(model, params, n_slots=1, max_len=48)
        fe = ServeFrontend(eng, queue_depth=8, prefix_cache=prefix_cache,
                           clock=ManualClock())
        hs = [fe.submit(Request(**vars(r))) for r in reqs]
        while fe.step():
            pass
        return [h.tokens for h in hs], eng

    cache = PrefixCache(cap=4, min_hit=4)
    warm, eng_w = serve(cache)
    cold, _ = serve(None)
    assert warm == cold, "prefix-spliced tokens diverged from cold prefill"
    assert cache.hits == 3 and cache.misses == 1  # first fills, rest hit
    assert cache.reused_tokens == 3 * 16
    assert eng_w.stats["prefix_hits"] == 3


def test_prefix_cache_lru_evicts(lm):
    model, params = lm
    eng = ServeEngine(model, params, n_slots=1, max_len=48)
    cache = PrefixCache(cap=1, min_hit=4)
    fe = ServeFrontend(eng, queue_depth=8, prefix_cache=cache,
                       clock=ManualClock())
    fe.submit(_req(0, 8, 2))
    while fe.step():
        pass
    fe.submit(_req(1, 8, 2))                      # different prompt: evicts
    while fe.step():
        pass
    assert len(cache) == 1 and cache.evictions == 1


def test_prefix_cache_rejected_for_ineligible_stack():
    """swa ring buffers violate the row-locality premise: the front-end
    refuses a prefix cache outright rather than serving wrong tokens."""
    cfg = tiny_cfg("gemma3-1b")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      n_slots=1, max_len=48)
    assert not eng.prefix_eligible()
    refusal = re.escape(errors.msg("prefix_ineligible", name=cfg.name))
    with pytest.raises(ValueError, match=refusal):
        ServeFrontend(eng, prefix_cache=PrefixCache())
    with pytest.raises(ValueError, match=refusal):
        eng.warmup(prompt_lens=[8], prefix=True)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def test_frontend_table_counts(lm):
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=1, clock=clk)
    hs = [fe.submit(_req(0, 4, 2)), fe.submit(_req(1, 4, 2)),
          fe.submit(_req(2, 4, 2))]               # third rejected
    while fe.step():
        clk.advance(0.1)
    tab = frontend_table(hs, wall=1.0)
    assert tab["requests"] == 3 and tab["done"] == 2
    assert tab["rejected"] == 1 and tab["expired"] == 0
    assert tab["tokens"] == 4
    assert tab["lat_p50_ms"] >= 0 and tab["ttft_p99_ms"] >= 0


# ---------------------------------------------------------------------------
# engine surfaces: the trace runner's reporting, warmup, guards
# ---------------------------------------------------------------------------

def test_static_trace_runner_and_percentiles(lm):
    """``ServeEngine.run`` over a whole request list returns completions
    in rid order, and ``percentile_table``/``format_table`` report them."""
    from repro.serve import percentile_table
    from repro.serve.engine import format_table
    model, params = lm
    trace = synthetic_trace(5, model.cfg.vocab_size, seed=4,
                            prompt_range=(4, 10), gen_range=(2, 5))
    comps = _engine(lm).run(trace)
    assert [c.rid for c in comps] == sorted(r.rid for r in trace)
    tab = percentile_table(comps, max(c.t_done for c in comps))
    assert tab["requests"] == 5
    assert tab["tokens"] == sum(r.gen for r in trace)
    txt = format_table([tab])
    assert txt.startswith("| requests") and "tok_per_s" in txt


def test_warmup_compiles_prefix_path(lm):
    """warmup(prefix=True) pre-compiles the splice path; the first real
    prefix hit then runs without raising and stays token-exact."""
    eng = _engine(lm, n_slots=1)
    eng.warmup(prompt_lens=[8, 10], prefix=True)
    assert eng.active_count() == 0                # reset afterwards
    fe = ServeFrontend(eng, queue_depth=2, prefix_cache=PrefixCache(),
                       clock=ManualClock())
    for i in range(2):
        fe.submit(_req(i, 8, 2))
        while fe.step():
            pass
    assert all(h.status is Status.DONE for h in fe.handles.values())


def test_engine_admit_and_cancel_guards(lm):
    eng = _engine(lm, n_slots=1, max_len=16)
    eng.begin()
    with pytest.raises(ValueError, match=re.escape(
            errors.msg("request_exceeds_max_len", rid=0, prompt=12, gen=8,
                       max_len=16))):
        eng.admit(_req(0, 12, 8), 0)              # 12 + 8 > 16
    with pytest.raises(ValueError, match=re.escape(
            errors.msg("cancel_free_slot", slot=0))):
        eng.cancel(0)                             # nothing running there


# ---------------------------------------------------------------------------
# chunked prefill through the front-end (serve/scheduler.py)
# ---------------------------------------------------------------------------

def test_frontend_chunked_matches_atomic_byte_identical(lm):
    """prefill_chunk must not change a single token: the chunked front-end
    streams byte-identically to the atomic one on the same trace."""
    model, params = lm
    trace = synthetic_trace(n=6, seed=5, rate=50.0, prompt_range=(4, 12),
                            gen_range=(2, 6), vocab=model.cfg.vocab_size)
    base = ServeFrontend(_engine(lm), queue_depth=8).run(trace)
    chunked = ServeFrontend(_engine(lm), queue_depth=8,
                            prefill_chunk=3).run(trace)
    assert all(h.status is Status.DONE for h in chunked)
    for b, c in zip(base, chunked):
        assert b.tokens == c.tokens, f"rid {b.rid}: chunked stream diverged"


def test_deadline_expired_mid_chunked_prefill(lm):
    """Deadline passes between chunks of a cold prefill: the partial
    prefill is discarded outright — ZERO tokens kept (contrast the atomic
    case, which keeps the prefill token), the cancel is counted, and the
    slot is immediately refillable."""
    eng = _engine(lm, n_slots=1)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk, prefill_chunk=2)
    h = fe.submit(_req(0, 9, 6, deadline=5.0))    # 9 tokens = 5 chunks
    assert h.status is Status.RUNNING             # PREFILLING: occupied,
    assert h.tokens == []                         # no token yet
    fe.step()                                     # one more chunk
    assert h.status is Status.RUNNING and h.tokens == []
    clk.advance(10.0)                             # deadline passes mid-way
    fe.step()
    assert h.status is Status.EXPIRED
    assert h.tokens == []                         # partial prefill discarded
    assert eng.stats["cancels"] == 1
    assert eng.active_count() == 0                # slot refillable
    g = fe.submit(_req(1, 4, 2))
    while not g.finished:
        fe.step()
    assert g.status is Status.DONE and len(g.tokens) == 2


def test_chunked_prefill_interleaves_with_decode(lm):
    """The tentpole behavior: while a long prompt prefills in chunks, a
    co-resident decoding slot keeps producing a token EVERY step — the
    long admit never freezes it."""
    eng = _engine(lm, n_slots=2)
    clk = ManualClock()
    fe = ServeFrontend(eng, queue_depth=4, clock=clk, prefill_chunk=2)
    short = fe.submit(_req(0, 2, 12))             # one chunk: installs at
    assert short.status is Status.RUNNING         # submit, decodes steadily
    assert len(short.tokens) == 1
    long = fe.submit(_req(1, 10, 2))              # 10 tokens = 4+ chunks
    assert long.status is Status.RUNNING and long.tokens == []
    while long.tokens == [] and not short.finished:
        before = len(short.tokens)
        fe.step()
        assert len(short.tokens) == before + 1, \
            "co-resident decode stalled during chunked prefill"
    assert eng.stats["chunk_steps"] >= 3
    while not (short.finished and long.finished):
        fe.step()
    assert short.status is Status.DONE and long.status is Status.DONE
