"""Slot-based continuous-batching serving engine.

Lifecycle (docs/serving.md):

  submit -> [queue] -> admit (bucketed prefill, write slot) -> decode ...
            -> retire (slot freed) -> refill mid-flight from the queue

Admission is non-atomic under the hood: ``begin_admit`` binds a request to
a slot (PREFILLING — occupied, but skipping decode lanes) and
``continue_admit`` consumes prompt tokens up to a budget, installing the
slot once the prompt is done. ``admit`` is the atomic composition; the
scheduling layer (serve/scheduler.py) time-slices ``continue_admit`` to
interleave chunked prefills with decode steps, so a long prompt never
stalls co-resident streams. Either way the computed tokens are identical.

One shared jitted decode step runs over all ``n_slots`` slots per iteration;
per-slot ``pos`` valid-lengths inside the cache drive the masked decode
attention (``kernels/flash_decode/decode_attention`` on TPU), so slots at
different sequence positions coexist in one step. Finished requests retire
and their slot is refilled immediately — no batch barrier, which is where
the throughput win over static batching comes from on ragged traces
(``benchmarks/bench_serve.py`` gates it).

Prompt-length bucketing bounds recompiles: prompts are right-padded to the
next bucket and prefilled with per-sample true ``lengths`` (causal attention
keeps cache rows < length exact — see ``lm_prefill``). Ragged prefill is
only sound for pure global-attention stacks; sliding-window archs fall back
to exact-length prefill (one compile per distinct length).

The engine dispatches on the config's **slot-cache contract**
(``serve/cache.py::cache_contract``, docs/serving.md) rather than
hard-coding KV: recurrent stacks (rwkv6, jamba hybrids) get a
``RecurrentSlotCache`` of fixed-size states — cold admits prefill the
longest chunk-quantized prefix exactly and walk the remainder through the
shared batch-1 decode step (bounded compiles without ragged soundness), and
retire *resets* the slot state instead of relying on the ``pos`` mask.

Pruned models plug in transparently: a ``cfg.pruned(...)`` config shrinks
``eff_qk`` and the slot cache's K rows shrink with it — the structured-
pruning serving payoff (smaller cache -> more slots per HBM byte).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import kernel_mesh
from repro.serve import errors
from repro.serve.cache import RecurrentSlotCache, SlotCache, cache_contract
from repro.spans import span


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray            # (P,) int32 prompt tokens
    gen: int                      # tokens to generate (>= 1)
    arrival: float = 0.0          # seconds relative to trace start
    frames: Optional[np.ndarray] = None   # (S, D) enc-dec memory frames
    deadline: Optional[float] = None      # absolute trace time; None = none


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray            # (gen,) generated tokens
    prompt_len: int
    arrival: float
    t_admit: float                # queue -> slot (prefill done)
    t_first: float                # first generated token available
    t_done: float                 # last token available

    @property
    def latency(self) -> float:
        return self.t_done - self.arrival

    @property
    def ttft(self) -> float:
        return self.t_first - self.arrival


@dataclasses.dataclass
class _Prefill:
    """In-flight (possibly chunked) admit for one slot: the batch-1 local
    cache being built and how much of the prompt it has absorbed. Held
    aside until the whole prompt is consumed, then installed with a single
    slot scatter — the shared (possibly sharded) cache never sees a
    half-prefilled slot, so chunk writes stay shard-local for free."""
    req: Request
    local: object = None           # batch-1 cache pytree (None pre-chunk-1)
    consumed: int = 0              # prompt tokens absorbed into ``local``
    first: Optional[int] = None    # first generated token (set at the end)
    prefix_cache: object = None    # insert/lookup target (None if unused)


@dataclasses.dataclass
class _Slot:
    rid: int = -1
    remaining: int = 0
    out: list = dataclasses.field(default_factory=list)
    req: Optional[Request] = None
    t_admit: float = 0.0
    t_first: float = 0.0
    pending: Optional[_Prefill] = None   # set while PREFILLING

    @property
    def free(self) -> bool:
        return self.req is None


def default_buckets(max_len: int, lo: int = 8):
    """Power-of-two prompt buckets up to max_len."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    return out + [max_len]


class ServeEngine:
    """Continuous-batching engine over a preallocated ``SlotCache``.

    Parameters
    ----------
    model, params : the (possibly pruned) model to serve.
    n_slots       : concurrent requests sharing the decode step.
    max_len       : per-slot sequence budget (prompt + generation).
    buckets       : prompt-length buckets (default: powers of two).
    mem_len       : enc-dec only — fixed encoder-memory length every
                    request's ``frames`` must match (cross K/V is unmasked).
    sharding      : optional ``serve.sharding.ServeSharding`` — run the
                    shared decode step under pjit with params placed by
                    ``distrib.sharding.param_specs`` and every slot-cache
                    leaf model-sharded per ``slot_specs`` (the slot axis is
                    the data axis). Admit/retire/cancel semantics are
                    unchanged; prefill outputs are pinned to the batch-1
                    local specs so the slot write is a sharded scatter.
    """

    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 buckets=None, mem_len: Optional[int] = None,
                 sharding=None):
        cfg = model.cfg
        if model.prefill is None or model.decode_step is None:
            raise ValueError(errors.msg("no_serving_path", name=cfg.name,
                                        family=cfg.family))
        # corp_prune returns host (numpy) leaves; indexing ops inside the
        # jitted prefill need device arrays
        self.model, self.cfg = model, cfg
        self.params = jax.tree.map(jnp.asarray, params)
        self.sharding = sharding
        if sharding is not None:
            from repro.distrib.sharding import param_specs, shardings_of
            self.params = jax.device_put(
                self.params, shardings_of(
                    param_specs(self.params, sharding.mesh), sharding.mesh))
        self.n_slots, self.max_len = n_slots, max_len
        self.mem_len = mem_len
        self.contract = cache_contract(cfg)
        # ragged (bucketed) prefill: sound iff every cache row < length is
        # independent of the padded tail — pure causal global attention
        self.ragged_ok = set(cfg.layer_kinds) == {"attn"}
        self.buckets = sorted(buckets) if buckets else \
            default_buckets(max_len)
        self.slots = [_Slot() for _ in range(n_slots)]
        self.tokens = np.zeros((n_slots,), np.int32)   # next decode inputs
        cache_cls = RecurrentSlotCache if self.contract == "recurrent" \
            else SlotCache
        self.slotcache = cache_cls(self._cache_template, n_slots,
                                   sharding=sharding, name=cfg.name,
                                   kv_heads=cfg.n_kv_heads)
        # sharded: pin out_shardings so the decode/prefill caches keep the
        # slot-cache layout (tokens replicated — every host reads them)
        if sharding is None:
            tok_out = glob_out = local_out = None
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            tok_out = NamedSharding(sharding.mesh, PartitionSpec())
            glob_out = (tok_out, self.slotcache._shardings)
            local_out = (tok_out, self.slotcache._local_shardings)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(2,),
                               out_shardings=glob_out)
        # batch-1 decode over a *local* (pre-scatter) cache: the prefix-hit
        # suffix path. NOT donated — the input may be a shared PrefixCache
        # entry whose buffers must survive the call.
        self._decode1 = jax.jit(self._decode_impl, out_shardings=local_out)
        self._prefill = jax.jit(self._prefill_impl, out_shardings=local_out)
        # an MoE decoder's step hands back the expert counters of the
        # lanes that hold a request behind the tokens, in the one fetch
        # the step makes
        self.moe_counts = cfg.moe is not None and cfg.family == "lm"
        self.stats = collections.Counter()
        self._t0 = None

    # -- jitted steps -------------------------------------------------------

    def _cache_template(self, batch: int):
        req = {"tokens": jax.ShapeDtypeStruct((batch, min(self.buckets)),
                                              jnp.int32)}
        if self.cfg.family == "encdec":
            if self.mem_len is None:
                raise ValueError(errors.msg("encdec_needs_mem_len"))
            req["frames"] = jax.ShapeDtypeStruct(
                (batch, self.mem_len, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return jax.eval_shape(
            lambda b: self.model.prefill(self.params, b, self.max_len)[1],
            req)

    def _argmax(self, logits):
        return jnp.argmax(logits[:, -1, : self.cfg.vocab_size],
                          axis=-1).astype(jnp.int32)

    def _kernel_mesh(self):
        """Sharded steps span the mesh: their kernels run per device."""
        return kernel_mesh(None if self.sharding is None
                           else self.sharding.mesh)

    def _prefill_impl(self, params, batch, lengths):
        with self._kernel_mesh():
            logits, cache = self.model.prefill(
                params, batch, self.max_len,
                lengths=lengths if self.ragged_ok else None)
        return self._argmax(logits), cache

    def _decode_impl(self, params, tok, cache, lanes=None):
        with self._kernel_mesh():
            if lanes is not None:
                logits, cache, counts = self.model.decode_step(
                    params, tok, cache, count_lanes=lanes)
                return jnp.concatenate([self._argmax(logits), counts]), cache
            logits, cache = self.model.decode_step(params, tok, cache)
        return self._argmax(logits), cache

    # -- slot management ----------------------------------------------------

    def _bucket(self, n: int) -> int:
        if not self.ragged_ok:
            return n                       # exact-length prefill
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(errors.msg("prompt_exceeds_bucket", n=n,
                                    bucket=self.buckets[-1]))

    def _stat_bucket(self, L: int) -> int:
        """Aggregation key for the ``prefill_b*`` stats counters: the
        smallest bucket covering ``L``. Exact-length fallback prefills
        (one compile per distinct length) used to key stats by the exact
        length, so a long ragged trace grew ``stats`` without bound;
        bucketing the *key* keeps the counter family bounded by the bucket
        table while the compiled shapes stay exact."""
        for b in self.buckets:
            if b >= L:
                return b
        return self.buckets[-1]

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.free]

    def active_count(self) -> int:
        return sum(not s.free for s in self.slots)

    def decoding_count(self) -> int:
        """Occupied slots actually in the decode phase. A PREFILLING slot
        (non-atomic admit in flight) is occupied but has no token to feed
        the shared decode step yet — it skips decode lanes until its
        prompt is consumed."""
        return sum((not s.free) and s.pending is None for s in self.slots)

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def begin(self, t0: Optional[float] = None):
        """Anchor the engine clock. ``run``/``warmup`` call this themselves;
        external drivers (the serving front-end) that call ``admit``/
        ``decode_step`` directly must call it once before serving."""
        self._t0 = time.perf_counter() if t0 is None else t0

    # prefix reuse needs a *replayable* contract: pure causal global
    # attention (cache rows are a pure function of the tokens at or before
    # them — any prefix is a rewind), or a recurrent state reused whole
    # (serve/prefix.py::usable_prefix_len). Enc-dec is excluded because the
    # encoder memory keys the cross attention, not the prompt tokens alone;
    # sliding-window ring buffers are neither rewindable nor snapshot-whole.
    def prefix_eligible(self) -> bool:
        return (self.ragged_ok and self.cfg.family == "lm") \
            or self.contract == "recurrent"

    def begin_admit(self, req: Request, slot: int, prefix_cache=None):
        """Bind ``req`` to ``slot`` without running any prefill work.

        First half of the non-atomic admit the scheduler
        (serve/scheduler.py) drives: validates the request, consults the
        prefix cache, and marks the slot PREFILLING — occupied (``free``
        is False) but skipping decode lanes until ``continue_admit``
        consumes the whole prompt. On a prefix hit the cached rows are
        adopted up-front: the KV contract rewinds the entry to the hit
        length (exact by causality, serve/prefix.py), the recurrent
        contract reuses the whole-prefix state snapshot as-is.
        """
        P = len(req.tokens)
        if P + req.gen > self.max_len:
            raise ValueError(errors.msg("request_exceeds_max_len",
                                        rid=req.rid, prompt=P, gen=req.gen,
                                        max_len=self.max_len))
        if self.cfg.family == "encdec":
            fr = np.asarray(req.frames)
            if fr.shape[0] != self.mem_len:
                raise ValueError(errors.msg(
                    "frames_mem_len_mismatch", rid=req.rid,
                    frames=fr.shape[0], mem_len=self.mem_len))
        use_prefix = prefix_cache is not None and self.prefix_eligible()
        recurrent = self.contract == "recurrent"
        st = _Prefill(req=req,
                      prefix_cache=prefix_cache if use_prefix else None)
        hit = prefix_cache.lookup(req.tokens, whole_entry=recurrent) \
            if use_prefix else None
        if hit is not None:
            entry, hit_len = hit
            if recurrent:
                st.local = entry.cache
            else:
                from repro.models.lm import override_cache_pos
                st.local = override_cache_pos(
                    entry.cache, jnp.full((1,), hit_len, jnp.int32))
            st.consumed = hit_len
            self.stats["prefix_hits"] += 1
            self.stats["prefix_reused_tokens"] += hit_len
            self.stats["prefix_suffix_tokens"] += P - hit_len
        s = self.slots[slot]
        if s.out:                      # slot previously served a request
            self.stats["refills"] += 1
        s.rid, s.req, s.out = req.rid, req, []
        s.remaining = req.gen
        s.pending = st
        self.stats["admits"] += 1

    def _first_chunk_len(self, n: int, P: int) -> int:
        """Prompt tokens the first prefill call of an admit consumes, given
        a budget of ``n`` (<= ``P``). Ragged stacks prefill any prefix
        (padded to a bucket); exact-length stacks quantize partial chunks
        to multiples of the smallest bucket so chunked serving cannot grow
        the compile count past the bucket table; recurrent stacks always
        leave >= 1 token for the batch-1 walk (matching the cold-admit
        path — their prefill never pads)."""
        if self.ragged_ok:
            return n
        if self.contract != "recurrent" and n >= P:
            return P                   # whole-prompt exact prefill
        cap = min(n, P - 1) if self.contract == "recurrent" else n
        lo = self.buckets[0]
        return max(1, lo * (cap // lo))

    def continue_admit(self, slot: int,
                       budget: Optional[int] = None) -> bool:
        """Consume up to ``budget`` prompt tokens of ``slot``'s in-flight
        admit (the whole remainder when None); True once the prompt is
        consumed and the slot is installed (first token on ``out``,
        decode-eligible).

        The chunk mechanics are pieces the engine already trusts: the
        first chunk is a bucketed/chunk-quantized *prefix prefill* — exact
        because every cache row carries only its own history (causal KV
        rows, swa ring slots keyed by absolute position, recurrent state)
        — and later chunks walk tokens one at a time through the batch-1
        decode step, identical to the prefix-splice suffix path. The local
        cache is installed with a single slot scatter at the end, so a
        half-prefilled slot never touches the shared (possibly sharded)
        cache.
        """
        s = self.slots[slot]
        st = s.pending
        if st is None:
            raise ValueError(errors.msg("continue_without_begin",
                                        slot=slot))
        req = st.req
        P = len(req.tokens)
        budget = P - st.consumed if budget is None else max(1, int(budget))
        nxt = None
        if st.local is None:           # first chunk: prefix prefill
            L0 = self._first_chunk_len(min(budget, P), P)
            if self.ragged_ok:
                toks = np.zeros((1, self._bucket(L0)), np.int32)
                toks[0, :L0] = req.tokens[:L0]
            else:
                toks = np.asarray(req.tokens[:L0], np.int32)[None]
            with span("engine.prefill", rid=req.rid, tokens=L0,
                      bucket=toks.shape[1]):
                batch = {"tokens": jnp.asarray(toks)}
                if self.cfg.family == "encdec":
                    batch["frames"] = jnp.asarray(
                        np.asarray(req.frames))[None]
                nxt, st.local = self._prefill(self.params, batch,
                                              jnp.asarray([L0], jnp.int32))
            self.stats[f"prefill_b{self._stat_bucket(L0)}"] += 1
            st.consumed = L0
            budget -= L0
            if st.prefix_cache is not None \
                    and self.contract == "recurrent" \
                    and L0 >= st.prefix_cache.min_hit:
                # chunk state inserted under its exact token prefix: the
                # whole-entry snapshot a later prompt extending it reuses
                from repro.serve.cache import cache_bytes
                st.prefix_cache.insert(req.tokens[:L0], st.local,
                                       cache_bytes(st.local))
        while budget > 0 and st.consumed < P:
            t = int(req.tokens[st.consumed])
            nxt, st.local = self._decode1(self.params,
                                          jnp.full((1, 1), t, jnp.int32),
                                          st.local)
            st.consumed += 1
            budget -= 1
        if st.consumed < P:
            self.stats["chunk_steps"] += 1
            return False
        with span("engine.prefill.wait"):
            st.first = int(nxt[0])
        self._install(slot, st)
        return True

    def _install(self, slot: int, st: _Prefill):
        """Prefill complete: insert into the prefix cache, scatter the
        local cache into the slot lane, and make the slot decode-eligible
        with its first generated token."""
        with span("engine.install", slot=slot):
            if st.prefix_cache is not None:
                from repro.serve.cache import cache_bytes
                st.prefix_cache.insert(st.req.tokens, st.local,
                                       cache_bytes(st.local))
            s = self.slots[slot]
            s.out = [st.first]
            s.remaining = st.req.gen - 1
            s.t_admit = s.t_first = self._now()
            self.tokens[slot] = st.first
            self.slotcache.write_slot(st.local, slot)
            s.pending = None

    def admit(self, req: Request, slot: int, prefix_cache=None):
        """Prefill ``req`` and install it into ``slot`` — the atomic
        composition of ``begin_admit`` + ``continue_admit`` with an
        unbounded budget (byte-identical streams either way; chunking via
        the scheduler changes *when* the work happens, never *what* is
        computed).

        With a ``prefix_cache`` (serve/prefix.py) on a prefix-eligible
        config, a prompt sharing a cached prefix skips recomputing it; the
        full prefill result is inserted back into the cache either way.
        """
        self.begin_admit(req, slot, prefix_cache=prefix_cache)
        self.continue_admit(slot)

    def decode_step(self):
        """One shared decode step over every slot; returns retired slots."""
        live = np.fromiter((not s.free and s.pending is None
                            for s in self.slots), bool, len(self.slots))
        active = int(live.sum())
        with span("engine.decode", step=self.stats["decode_steps"],
                  lanes=active):
            # handed over as numpy: jnp.asarray of a list of bools costs
            # ~1 ms of host time a step
            counted = (live,) if self.moe_counts else ()
            nxt, cache = self._decode(self.params,
                                      jnp.asarray(self.tokens[:, None]),
                                      self.slotcache.cache, *counted)
            self.slotcache.cache = cache
            with span("engine.decode.wait"):
                nxt = np.asarray(nxt)
            if self.moe_counts:
                # summed over the step's MoE layers
                self.stats["moe_pairs"] += int(nxt[self.n_slots])
                self.stats["moe_experts_hit"] += int(nxt[self.n_slots + 1])
            self.stats["decode_steps"] += 1
            self.stats["decode_lanes"] += active
            self.stats["max_concurrent"] = max(self.stats["max_concurrent"],
                                               active)
            retired = []
            for i, s in enumerate(self.slots):
                # PREFILLING slots skip decode lanes: their lane computed
                # garbage (stale token over a stale cache row, like a free
                # slot's) and the install scatter overwrites the row wholesale
                if s.free or s.pending is not None:
                    continue
                s.out.append(int(nxt[i]))
                self.tokens[i] = nxt[i]
                s.remaining -= 1
                if s.remaining == 0:
                    retired.append(i)
            return retired

    def retire(self, slot: int) -> Completion:
        """Free ``slot`` and return its finished request's Completion.
        The slot is immediately refillable (the next admit overwrites it)."""
        s = self.slots[slot]
        comp = Completion(
            rid=s.rid, tokens=np.asarray(s.out, np.int32),
            prompt_len=len(s.req.tokens), arrival=s.req.arrival,
            t_admit=s.t_admit, t_first=s.t_first, t_done=self._now())
        s.rid, s.req, s.remaining, s.pending = -1, None, 0, None
        if self.contract == "recurrent":
            self.slotcache.reset_slot(slot)
        return comp

    def cancel(self, slot: int) -> List[int]:
        """Retire hook for the front-end: drop ``slot``'s request mid-
        generation (deadline expiry / caller cancel) and return the partial
        tokens produced so far. The slot is refillable on the next admit,
        exactly like a normal retire — its stale cache lanes are inert
        (masked by ``pos``, or reset under the recurrent contract) until
        overwritten. Cancelling a PREFILLING slot discards the partial
        prefill outright (its local cache was never installed): zero
        tokens kept, slot immediately refillable."""
        s = self.slots[slot]
        if s.free:
            raise ValueError(errors.msg("cancel_free_slot", slot=slot))
        partial = list(s.out)
        s.rid, s.req, s.remaining, s.pending = -1, None, 0, None
        if self.contract == "recurrent":
            self.slotcache.reset_slot(slot)
        self.stats["cancels"] += 1
        return partial

    # -- driver -------------------------------------------------------------

    def run(self, requests: List[Request], *, log=None,
            prefill_chunk: Optional[int] = None) -> List[Completion]:
        """Serve a trace to completion; returns completions in rid order.

        ``prefill_chunk`` hands the interleaving to a scheduler with that
        per-iteration token budget (serve/scheduler.py): cold admits
        prefill at most that many prompt tokens per engine iteration, so
        occupied slots take a decode step between chunks. Streams are
        byte-identical either way.
        """
        from repro.serve.scheduler import Scheduler
        sched = Scheduler(self, prefill_chunk=prefill_chunk)
        queue = collections.deque(
            sorted(requests, key=lambda r: (r.arrival, r.rid)))
        done: dict = {}
        self.begin()
        while queue or self.active_count():
            now = self._now()
            for slot in sched.advance():   # resume in-flight chunked admits
                if self.slots[slot].remaining == 0:
                    comp = self.retire(slot)
                    done[comp.rid] = comp  # gen==1: prefill token only
            free = self.free_slots()
            while queue and queue[0].arrival <= now and free:
                slot = free[0]
                started = sched.start(queue.popleft(), slot)
                if started and self.slots[slot].remaining == 0:
                    comp = self.retire(slot)
                    done[comp.rid] = comp  # gen==1: prefill token only
                else:
                    free.pop(0)
            if not sched.should_decode():
                if not self.active_count() and queue:
                    # idle until the next arrival
                    time.sleep(max(0.0, min(queue[0].arrival - self._now(),
                                            1e-3)))
                continue
            for slot in self.decode_step():
                s = self.slots[slot]
                if log:
                    log(f"[serve] rid={s.rid} done "
                        f"({len(s.out)} tok, slot {slot})")
                comp = self.retire(slot)
                done[comp.rid] = comp
        return [done[r.rid] for r in sorted(requests, key=lambda r: r.rid)]

    def warmup(self, prompt_lens=(8,), gen: int = 2, prefix: bool = False,
               prefill_chunk: Optional[int] = None):
        """Compile prefill (per bucket), decode, and the slot write outside
        any timed region; resets the engine afterwards. ``prefix=True``
        additionally compiles the batch-1 suffix decode the prefix-hit
        admit path uses; ``prefill_chunk`` warms the chunked-prefill path
        instead (the same batch-1 decode, plus the chunk-sized first-chunk
        prefill shapes, by running the warm trace through the scheduler)."""
        if prefix and not self.prefix_eligible():
            raise ValueError(errors.msg("prefix_ineligible",
                                        name=self.cfg.name))
        if prefix or prefill_chunk is not None:
            local = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 self._cache_template(1))
            # the splice path = pos rewind (KV contract only) + batch-1
            # suffix decode — the same walk chunked admits take; compile
            # both so the first prefix hit / chunk isn't charged compile
            # time
            if self.contract != "recurrent":
                from repro.models.lm import override_cache_pos
                local = override_cache_pos(local, jnp.zeros((1,), jnp.int32))
            self._decode1(self.params, jnp.zeros((1, 1), jnp.int32), local)
        reqs = []
        for i, b in enumerate(sorted({self._bucket(p)
                                      for p in prompt_lens})):
            # a bucket-sized prompt can overflow the per-slot budget
            # (b == max_len); shrink the prompt — it still rounds back up
            # to the same bucket, so the same prefill shape compiles
            p = max(1, min(b, self.max_len - gen))
            frames = None
            if self.cfg.family == "encdec":
                frames = np.zeros((self.mem_len, self.cfg.d_model),
                                  np.float32)
            reqs.append(Request(rid=-(i + 1),
                                tokens=np.zeros((p,), np.int32), gen=gen,
                                frames=frames))
        self.run(reqs, prefill_chunk=prefill_chunk)
        self.reset()

    def reset(self):
        self.slotcache.reset()
        self.tokens[:] = 0
        self.slots = [_Slot() for _ in range(self.n_slots)]
        self.stats = collections.Counter()

    @property
    def cache_bytes(self) -> int:
        return self.slotcache.bytes

    @property
    def device_cache_bytes(self) -> int:
        """Largest per-device slot-cache footprint (== ``cache_bytes``
        unsharded; ~1/model-axis of it under a ``sharding`` — the number
        benchmarks/bench_serve_sharded.py gates)."""
        return self.slotcache.device_bytes


# ---------------------------------------------------------------------------
# static fixed-batch baseline (the pre-engine serve loop, trace-shaped)
# ---------------------------------------------------------------------------

def run_static_trace(model, params, requests: List[Request], *,
                     n_slots: int, max_len: int,
                     buckets=None) -> List[Completion]:
    """Serve the trace in fixed batches of ``n_slots``: each batch pads every
    prompt to the longest and decodes until the *longest* generation in the
    batch finishes — the batch barrier continuous batching removes."""
    cfg = model.cfg
    if set(cfg.layer_kinds) != {"attn"}:
        raise ValueError(errors.msg("static_trace_ineligible"))
    buckets = sorted(buckets) if buckets else default_buckets(max_len)
    vocab = cfg.vocab_size

    @jax.jit
    def prefill(params, batch, lengths):
        logits, cache = model.prefill(params, batch, max_len,
                                      lengths=lengths)
        return jnp.argmax(logits[:, -1, :vocab], -1).astype(jnp.int32), cache

    @jax.jit
    def decode(params, tok, cache):
        logits, cache = model.decode_step(params, tok, cache)
        return jnp.argmax(logits[:, -1, :vocab], -1).astype(jnp.int32), cache

    order = sorted(requests, key=lambda r: (r.arrival, r.rid))
    groups = [order[i:i + n_slots] for i in range(0, len(order), n_slots)]

    def bucket_of(group):
        Lmax = max(len(r.tokens) for r in group)
        return next((b for b in buckets if b >= Lmax), Lmax)

    # compile-warm every prefill bucket this trace will use (and the decode
    # step) outside the timed region, matching the engine's warmup — the
    # measured gap must be the batch barrier, not compile time
    for L in sorted({bucket_of(g) for g in groups}):
        tok, cache = prefill(params, {"tokens": jnp.zeros((n_slots, L),
                                                          jnp.int32)},
                             jnp.ones((n_slots,), jnp.int32))
        decode(params, tok[:, None], cache)

    done = []
    t0 = time.perf_counter()
    for group in groups:
        while time.perf_counter() - t0 < max(r.arrival for r in group):
            time.sleep(1e-4)               # batch can't start early
        B = n_slots
        L = bucket_of(group)
        toks = np.zeros((B, L), np.int32)
        lens = np.ones((B,), np.int32)
        for j, r in enumerate(group):
            toks[j, :len(r.tokens)] = r.tokens
            lens[j] = len(r.tokens)
        first, cache = prefill(params, {"tokens": jnp.asarray(toks)},
                               jnp.asarray(lens))
        outs = [[int(t)] for t in np.asarray(first)[:len(group)]]
        tok = first
        for _ in range(max(r.gen for r in group) - 1):
            tok, cache = decode(params, tok[:, None], cache)
            for j in range(len(group)):
                outs[j].append(int(tok[j]))
        t_done = time.perf_counter() - t0
        for j, r in enumerate(group):      # everyone waits for the batch
            done.append(Completion(
                rid=r.rid, tokens=np.asarray(outs[j][:r.gen], np.int32),
                prompt_len=len(r.tokens), arrival=r.arrival,
                t_admit=t_done, t_first=t_done, t_done=t_done))
    return sorted(done, key=lambda c: c.rid)


# ---------------------------------------------------------------------------
# synthetic ragged traces + reporting
# ---------------------------------------------------------------------------

def _substream(seed: int, salt: int) -> np.random.RandomState:
    """Independent RNG stream per trace field (seed determinism contract)."""
    return np.random.RandomState((seed * 0x9E3779B1 + salt) & 0xFFFFFFFF)


def synthetic_trace(n: int, vocab: int, *, seed: int = 0,
                    prompt_range=(8, 48), gen_range=(4, 48),
                    rate: Optional[float] = None,
                    deadline_range=None, deadline_frac: float = 1.0,
                    prefix_len: int = 0, mem_len: Optional[int] = None,
                    d_model: int = 0) -> List[Request]:
    """Ragged arrival trace: mixed prompt/gen lengths, optional Poisson
    arrivals at ``rate`` req/s (default: all available at t=0).

    Every field draws from its own seed-derived substream, so ``seed``
    fully determinizes the trace field-by-field: toggling ``rate`` cannot
    reshuffle prompt/gen lengths, and adding deadlines cannot perturb the
    arrival timeline (previously one shared stream coupled every draw
    order — ``tests/test_serve_properties.py`` pins the contract).

    ``deadline_range=(lo, hi)`` gives a ``deadline_frac`` fraction of
    requests an absolute deadline ``arrival + U(lo, hi)`` seconds (the
    rest run un-deadlined — the "deadline mix"). ``prefix_len > 0``
    prepends one shared system prompt of that many tokens to every request
    (``prompt_range`` then sizes the per-request *suffix*) — the
    prefix-cache workload. ``mem_len`` (with ``d_model``) attaches
    per-request encoder-memory frames of that fixed length — the enc-dec
    workload (``ServeEngine(mem_len=...)``).
    """
    rng_arr = _substream(seed, 1)
    rng_len = _substream(seed, 2)
    rng_tok = _substream(seed, 3)
    rng_dl = _substream(seed, 4)
    rng_fr = _substream(seed, 5)
    arrivals = np.zeros(n) if rate is None else \
        np.cumsum(rng_arr.exponential(1.0 / rate, size=n))
    shared = rng_tok.randint(0, vocab, size=prefix_len).astype(np.int32) \
        if prefix_len else None
    reqs = []
    for i in range(n):
        P = int(rng_len.randint(prompt_range[0], prompt_range[1] + 1))
        G = int(rng_len.randint(gen_range[0], gen_range[1] + 1))
        toks = rng_tok.randint(0, vocab, size=P).astype(np.int32)
        if shared is not None:
            toks = np.concatenate([shared, toks])
        deadline = None
        if deadline_range is not None:
            budget = float(rng_dl.uniform(*deadline_range))
            if rng_dl.uniform() < deadline_frac:
                deadline = float(arrivals[i]) + budget
        frames = None
        if mem_len is not None:
            assert d_model > 0, "mem_len= needs d_model="
            frames = rng_fr.randn(mem_len, d_model).astype(np.float32)
        reqs.append(Request(rid=i, tokens=toks, gen=G,
                            arrival=float(arrivals[i]), deadline=deadline,
                            frames=frames))
    return reqs


def percentile_table(completions: List[Completion], wall: float) -> dict:
    """p50/p99 latency + aggregate throughput over a served trace."""
    lat = np.asarray([c.latency for c in completions])
    ttft = np.asarray([c.ttft for c in completions])
    total = int(sum(len(c.tokens) for c in completions))
    return {
        "requests": len(completions),
        "tokens": total,
        "wall_s": wall,
        "tok_per_s": total / max(wall, 1e-9),
        "lat_p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "lat_p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
        "ttft_p99_ms": float(np.percentile(ttft, 99)) * 1e3,
    }


def format_table(rows: List[dict], keys=None) -> str:
    """Markdown table from a list of same-keyed dicts."""
    keys = keys or list(rows[0])
    def fmt(v):
        return f"{v:.1f}" if isinstance(v, float) else str(v)
    out = ["| " + " | ".join(keys) + " |",
           "|" + "---|" * len(keys)]
    for r in rows:
        out.append("| " + " | ".join(fmt(r.get(k, "-")) for k in keys)
                   + " |")
    return "\n".join(out)
