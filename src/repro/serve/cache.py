"""Preallocated per-slot KV cache for the continuous-batching engine.

The engine runs ONE shared jitted decode step over all ``n_slots`` slots; a
request occupies a slot for its lifetime, and admitting a new request only
overwrites that slot's rows — no reshape, no reallocation, no recompile.

Layout
------
The global cache is the model's own prefill-cache pytree with the batch axis
widened to ``n_slots``. The batch axis is NOT uniformly the leading axis:
scanned-segment leaves are stacked ``(reps, B, max_len, ...)`` and enc-dec
decoder stacks are ``(n_layers, B, ...)``, so the per-leaf batch axis is
*inferred structurally* — ``jax.eval_shape`` of the prefill at two batch
sizes, and the axis whose dim differs is the batch axis. Slot writes are then
``dynamic_update_index_in_dim`` along that axis per leaf (donated, so XLA
updates in place).

Per-slot validity lives in the cache itself: every layer cache carries a
``pos`` (B,) valid-length which the decode attention turns into its key mask
(``key_idx <= pos``) — exactly the masked-cache contract of
``kernels/flash_decode/decode_attention``. Free slots simply keep decoding
into discarded lanes; their ``pos`` may walk past ``max_len``, where the
scatter drops out-of-bounds writes (jax semantics), so stale slots are inert
until the next admit overwrites them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

RECURRENT_KINDS = frozenset({"mamba", "rwkv"})


def cache_contract(cfg) -> str:
    """Classify a config's slot-cache contract: what a slot holds, how it
    grows, and what admit/retire must do (docs/serving.md).

    - ``"kv"``        — per-token KV rows up to ``max_len``; bytes grow with
      the budget; freed slots are inert under the ``pos`` mask.
    - ``"recurrent"`` — fixed-size wkv6/SSM state (hybrids with any
      mamba/rwkv layer count too: one contaminated layer breaks the KV
      row-locality premise for the whole stack); bytes constant in
      ``max_len``; retire must *reset* the state (a lossy whole-history
      summary has no mask to hide behind).
    - ``"encdec"``    — decoder self-attn KV plus a fixed cross-attn memory
      keyed by the encoder frames, not the prompt tokens.
    """
    if cfg.family == "encdec":
        return "encdec"
    if set(cfg.layer_kinds) & RECURRENT_KINDS:
        return "recurrent"
    return "kv"


def _infer_batch_axes(tree1, tree2):
    """Per-leaf batch axis: the first dim that differs between the two
    ShapeDtypeStruct trees (evaluated at two different batch sizes)."""
    def axis_of(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise ValueError(f"no batch axis found in cache leaf {a.shape}")
    return jax.tree.map(axis_of, tree1, tree2)


def cache_bytes(tree) -> int:
    """Total bytes held by a cache pytree."""
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


class SlotCache:
    """n_slots-wide preallocated decode cache with per-slot writes.

    Built lazily from the *shape* of the model's prefill cache (no forward
    pass): ``template_fn(batch)`` must return the prefill-cache
    ShapeDtypeStruct tree at that batch size (time axis already padded to
    ``max_len``).

    With a ``sharding`` (``serve.sharding.ServeSharding``) every leaf is
    explicitly placed on the mesh: the inferred slot axis shards over the
    data axis, payload dims over the model axis (``slot_specs`` — the
    per-contract table in docs/serving.md). The donated per-slot scatter
    is unchanged; pinning ``out_shardings`` keeps each write shard-local,
    and the batch-1 ``local_specs`` (data-replicated, model-sharded) are
    what the engine pins its prefill outputs to, so admit is a *sharded*
    scatter: the local cache arrives already split over the model axis and
    ``dynamic_update_index_in_dim`` runs per shard with no resharding.
    """

    def __init__(self, template_fn, n_slots: int, *, sharding=None,
                 name: str = "slot-cache", kv_heads=None):
        self.n_slots = n_slots
        self.kv_heads = kv_heads
        sds1, sds2 = template_fn(1), template_fn(2)
        self.batch_axes = _infer_batch_axes(sds1, sds2)
        self._template = template_fn(n_slots)
        self.sharding = sharding
        self.specs = self.local_specs = None
        self._shardings = self._local_shardings = None
        if sharding is not None:
            from repro.distrib.sharding import shardings_of
            from repro.serve.sharding import slot_specs
            kw = dict(data_axis=sharding.data_axis,
                      model_axis=sharding.model_axis, name=name,
                      kv_heads=kv_heads)
            self.specs = slot_specs(self._template, self.batch_axes,
                                    sharding.mesh, **kw)
            self.local_specs = slot_specs(sds1, self.batch_axes,
                                          sharding.mesh, **kw)
            self._shardings = shardings_of(self.specs, sharding.mesh)
            self._local_shardings = shardings_of(self.local_specs,
                                                 sharding.mesh)
        self.cache = self._zeros()
        # donate the global cache so XLA updates the slot rows in place
        # (the batch-1 local cache has different shapes, so it can't donate)
        self._write = jax.jit(self._write_impl, donate_argnums=(0,),
                              out_shardings=self._shardings)

    def _zeros(self):
        z = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         self._template)
        return z if self._shardings is None else \
            jax.device_put(z, self._shardings)

    def reset(self):
        """Drop all slot contents (e.g. after compile warmup)."""
        self.cache = None           # freed first: never two caches at once
        self.cache = self._zeros()

    def _write_impl(self, global_c, local_c, slot):
        def put(g, l, ax):
            row = jax.lax.index_in_dim(l, 0, ax, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                g, row.astype(g.dtype), slot, ax)
        return jax.tree.map(put, global_c, local_c, self.batch_axes)

    def write_slot(self, local_cache, slot: int):
        """Admit: copy a batch-1 prefill cache into slot ``slot``."""
        self.cache = self._write(self.cache, local_cache,
                                 jnp.int32(slot))

    @property
    def bytes(self) -> int:
        return cache_bytes(self.cache)

    @property
    def slot_bytes(self) -> int:
        """Bytes one slot occupies (the per-request cache cost)."""
        return self.bytes // self.n_slots

    @property
    def device_bytes(self) -> int:
        """Largest per-device resident bytes of the live cache — the number
        mesh sharding exists for (== ``bytes`` unsharded). Measured from
        the arrays' addressable shards, like the calibration footprint
        gate (benchmarks/bench_calib_sharded.py)."""
        total = 0
        for leaf in jax.tree.leaves(self.cache):
            shards = getattr(leaf, "addressable_shards", None)
            total += max(s.data.nbytes for s in shards) if shards \
                else leaf.nbytes
        return int(total)


class RecurrentSlotCache(SlotCache):
    """Slot cache for the *recurrent* contract: each slot holds a fixed-size
    wkv6/SSM state instead of growing KV rows.

    Admit is the same donated per-slot scatter as ``SlotCache`` (recurrent
    states have no time axis, so the whole lane is replaced), decode is the
    same shared step — the difference is retire. A freed KV slot is inert
    behind its ``pos`` mask, but a recurrent state is a lossy summary of the
    whole history with no mask to hide behind, so ``reset_slot`` scatters
    the empty-history (zero) state back into the lane. ``slot_bytes`` is
    constant in ``max_len`` — the cheaper cache contract the recurrent
    bench row gates (benchmarks/bench_serve.py).
    """

    def __init__(self, template_fn, n_slots: int, *, sharding=None,
                 name: str = "slot-cache", kv_heads=None):
        super().__init__(template_fn, n_slots, sharding=sharding, name=name,
                         kv_heads=kv_heads)
        # batch-1 empty-history state, reused by every reset_slot scatter
        # (placed like a prefill output, so the reset stays shard-local)
        blank = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             template_fn(1))
        self._blank = blank if self._local_shardings is None else \
            jax.device_put(blank, self._local_shardings)

    def reset_slot(self, slot: int):
        """Retire/cancel: return ``slot``'s lane to the empty-history
        state (the state the next admit's scatter expects to replace)."""
        self.write_slot(self._blank, slot)
