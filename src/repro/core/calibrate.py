"""Single-pass fused calibration engine for CORP (Alg. 3 inputs).

CORP's entire cost is the calibration pass, so how statistics stream out of
activations decides whether the paper's "under 20 minutes on one device"
claim holds. ``CalibrationEngine`` owns that hot path:

  * **one forward per batch** — a single jitted step runs the model once and
    reduces *every* unit's statistics (MLP/MoE/mamba moments, attention
    logit energies for pass 1; ridge-system inputs for pass 2) from the
    taps of that one forward, instead of a per-unit loop of separately
    jitted steps that each re-run the model;
  * **donated on-device accumulator** — the statistics pytree stays on
    device across the whole pass and the previous accumulator's buffers are
    donated to each step (``donate_argnums=0``), so accumulation is
    in-place with no host round-trip per batch; only the final result is
    fetched;
  * **checkpointable** — the accumulator is an ordinary pytree of sums, so
    any prefix of the stream is a valid checkpoint. Pass a
    ``repro.distrib.fault.CalibrationCheckpointer`` to make a long pass
    resumable (batches are deterministic-by-index; the restored batch
    cursor skips what was already consumed; saves run on a background
    thread so the pass never blocks on disk);
  * **one-traversal** — ``phase="1+2"`` accumulates pass-1 statistics AND
    speculative pass-2 cross-moments (against fixed top-k candidate
    keep-sets, ``spec_plan=``) from the *same* forward, so when the final
    keep-sets land inside the candidates — the common case — CORP needs no
    second traversal of the calibration set (``corp_prune(...,
    one_traversal=True)``; design + margin policy in docs/pipeline.md);
  * **second moments through the Pallas gram kernel** — the per-unit
    ``X^T X`` reductions inside the step dispatch to
    ``repro.kernels.gram`` (streaming MXU kernel on TPU, zero-padded for
    arbitrary shapes, tile sizes autotuned per shape; plain-jnp reference
    elsewhere);
  * **bf16 activation streaming** — ``stats_dtype="bfloat16"`` emits the
    model's activation taps in bf16 and streams them into the gram kernel
    as-is, halving calibration HBM traffic; every accumulator stays fp32
    (the kernel casts per tile inside VMEM). Sigma tolerance vs the fp32
    stream is tested in ``tests/test_bf16_streaming.py`` and studied in
    docs/kernels.md;
  * **mesh-sharded** — pass ``mesh=`` and the fused step runs under pjit
    with an explicit sharding for every statistic leaf
    (``repro.distrib.sharding.stats_specs``): per-unit covariance/Gram
    blocks are column-sharded over the mesh's model axis, batch-axis
    contributions reduce via psum inside the compiled step, and the dense
    second moments route through the *per-shard* Pallas gram path
    (``gram_sharded`` — zero-padding on local tiles). No device ever holds
    a replicated full Sigma, which is what lets a 671B-config calibration
    pass fit (one dense-FFN Sigma at d_ff=18432 is 1.3 GB fp32 replicated,
    but only 1.3/m GB per device on an m-way model axis). See
    docs/calibration.md for the layout diagram.

Usage::

    engine = CalibrationEngine(model, units, phase=1)
    stats  = engine.run(params, calib_batches())            # pass 1
    engine2 = CalibrationEngine(model, units, phase=2, plan=plan)
    p2     = engine2.run(params, calib_batches())           # pass 2

    # sharded: same API, statistics land model-sharded on the mesh
    mesh = repro.launch.mesh.make_mesh((2, 4))              # data x model
    stats = CalibrationEngine(model, units, phase=1, mesh=mesh) \\
        .run(params, calib_batches())

Every statistic is a linear reduction, so the sharded engine is bitwise a
partitioning of the single-device one (same sums, same order per shard);
``tests/test_sharded_calibration.py`` asserts fp32 parity on a forced
4-device host mesh, and that each device holds fewer statistic bytes than
the single-device engine.
"""
from __future__ import annotations

import hashlib
import itertools
from typing import Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stats as stats_mod
from repro.core.units import Unit
from repro.distrib import sharding as dist_sharding
from repro.kernels import kernel_mesh
from repro.models import common as model_common
from repro.spans import span


class CalibrationEngine:
    """Fused streaming statistics gatherer for one calibration pass.

    Args:
      model: model object exposing ``apply(params, batch, taps=...)``.
      units: prunable units whose statistics to gather (all in one forward).
      phase: 1 (ranking/MLP moments + attention energies), 2 (attention
        compensation ridge inputs; requires ``plan``), or ``"1+2"``
        (one-traversal mode: pass-1 statistics plus *speculative* pass-2
        cross-moments against fixed candidate keep-sets; requires
        ``spec_plan``). The ``"1+2"`` accumulator is
        ``{"p1": <pass-1 tree>, "p2spec": <speculative tree>}`` — see
        ``repro.core.stats.spec_pass2_reduce`` / ``spec_reconstruct`` and
        docs/pipeline.md.
      plan: phase-2 only — ``{unit.name: (keep, prune)}`` index arrays.
      spec_plan: phase-"1+2" only — ``{unit.name: (..., G, c) candidate
        keep-indices}`` (``repro.core.ranking.candidate_attn``), fixed for
        the whole traversal.
      donate: donate the accumulator's buffers to each step (in-place
        accumulation). Disable when the caller needs the pre-step
        accumulator to survive a failing step (see ``fail_hook``).
      mesh: optional ``jax.sharding.Mesh`` (or a pre-built
        ``repro.distrib.sharding.CalibSharding``). When given, the fused
        step is jitted with ``stats_specs`` out-shardings: every per-unit
        covariance/Gram block is column-sharded over ``model_axis``, batch
        contributions psum-reduce over the data axes, and params/batches
        are placed per ``param_specs``/``batch_specs``. Statistics are
        numerically identical to the unsharded engine (linear reductions);
        only their device layout changes.
      model_axis: mesh axis name that partitions statistic columns
        (ignored without ``mesh``).
      stats_dtype: dtype activation taps are *streamed* in ("float32"
        default, "bfloat16" to halve calibration HBM traffic). Every
        statistic still accumulates in fp32 — the gram kernel casts tiles
        inside VMEM, the other reductions cast at their inputs — so only
        the per-tap rounding differs (docs/kernels.md quantifies the Sigma
        tolerance; tests/test_bf16_streaming.py holds it).

    Attributes:
      fingerprint: hash of what this engine accumulates (phase, unit set,
        pass-2 plan, and — when sharded — the mesh layout). Stored with
        every statistics checkpoint; see ``CalibrationCheckpointer``.
      stat_shardings: sharded mode only — the ``NamedSharding`` pytree of
        the accumulator, available after ``init_stats``/``run`` started
        (None before, and always None unsharded).
    """

    def __init__(self, model, units: List[Unit], *, phase=1,
                 plan: Optional[Dict] = None,
                 spec_plan: Optional[Dict] = None, donate: bool = True,
                 mesh=None, model_axis: str = "model",
                 stats_dtype="float32"):
        assert phase in (1, 2, "1+2"), phase
        assert phase != 2 or plan is not None, "phase 2 needs a keep/prune plan"
        assert phase != "1+2" or spec_plan is not None, \
            'phase "1+2" needs a speculative candidate plan'
        self.model = model
        self.units = list(units)
        self.phase = phase
        self.stats_dtype = jnp.dtype(stats_dtype)
        self.plan = None if plan is None else {
            k: tuple(jnp.asarray(a) for a in v) for k, v in plan.items()}
        self.spec_plan = None if spec_plan is None else {
            k: jnp.asarray(v) for k, v in spec_plan.items()}
        if mesh is None:
            self.shard = None
        elif isinstance(mesh, dist_sharding.CalibSharding):
            self.shard = mesh
        else:
            self.shard = dist_sharding.CalibSharding(mesh, model_axis)

        def reduce_fn(params, batch):
            taps = {}
            # entered at trace time: taps stream in stats_dtype end-to-end,
            # and a sharded step runs its kernels once per device
            with model_common.tap_dtype(self.stats_dtype), kernel_mesh(
                    None if self.shard is None else self.shard.mesh):
                model.apply(params, batch, taps=taps)
            if phase == 1:
                return stats_mod.pass1_reduce(taps, self.units, model.cfg,
                                              shard=self.shard)
            if phase == 2:
                return stats_mod.pass2_reduce(taps, self.units, self.plan)
            # "1+2": both reductions from the SAME forward's taps — the
            # one-traversal mode's whole point
            return {"p1": stats_mod.pass1_reduce(taps, self.units, model.cfg,
                                                 shard=self.shard),
                    "p2spec": stats_mod.spec_pass2_reduce(
                        taps, self.units, self.spec_plan)}

        def step(acc, params, batch):
            return jax.tree.map(jnp.add, acc, reduce_fn(params, batch))

        self._reduce = reduce_fn
        self._step_fn = step
        self._donate = donate
        self.stat_shardings = None
        self._batch_cache = None
        if self.shard is None:
            self._step = jax.jit(step, donate_argnums=(0,) if donate else ())
        else:
            self._step = None   # built by init_stats (needs stat shapes)
        self.fingerprint = self._fingerprint()

    def _fingerprint(self) -> str:
        """Identity of what this engine accumulates — phase, unit set,
        (for pass 2) the exact keep/prune plan, (for phase "1+2") the exact
        speculative candidate sets, and (when sharded) the mesh layout.
        Stored with every stats checkpoint so a reused checkpoint directory
        can never resume statistics gathered for a different configuration
        — including a checkpoint written under a *different mesh*, whose
        shard-local accumulation order (and donation layout) this engine
        cannot reproduce — or under a different streaming dtype, whose
        per-tap rounding differs. Phase "1+2" hashes differently from both
        1 and 2 (and per candidate set), so speculative checkpoints are
        rejected by two-pass engines and vice versa."""
        h = hashlib.sha256()
        h.update(f"phase={self.phase};stats_dtype={self.stats_dtype}"
                 .encode())
        for u in self.units:
            h.update(f";{u.name}:{u.kind}:{u.attn_class}".encode())
        if self.plan is not None:
            for k in sorted(self.plan):
                h.update(f";plan:{k}".encode())
                for a in self.plan[k]:
                    h.update(np.asarray(a).tobytes())
        if self.spec_plan is not None:
            for k in sorted(self.spec_plan):
                h.update(f";spec:{k}".encode())
                h.update(np.asarray(self.spec_plan[k]).tobytes())
        if self.shard is not None:
            mesh = self.shard.mesh
            h.update(f";mesh={tuple(mesh.axis_names)}"
                     f"x{tuple(mesh.devices.shape)}"
                     f":{self.shard.model_axis}".encode())
        return h.hexdigest()[:16]

    # -- accumulator lifecycle ------------------------------------------------

    def init_stats(self, params, batch):
        """Zeros pytree matching one batch's statistics (via eval_shape —
        no forward is executed).

        Unsharded: plain device zeros. Sharded: computes ``stats_specs``
        for the statistic shapes, builds the pjit-ed step with those
        out-shardings, and returns zeros already placed shard-by-shard
        (so the first donated step never reshards the accumulator).
        """
        shapes = jax.eval_shape(self._reduce, params, batch)
        if self.shard is None:
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        specs = dist_sharding.stats_specs(shapes, self.shard.mesh,
                                          model_axis=self.shard.model_axis)
        shardings = dist_sharding.shardings_of(specs, self.shard.mesh)
        # rebuild the jitted step only when the layout actually changed —
        # re-wrapping jax.jit would discard its compile cache, retracing
        # the whole model per run()/resume
        if self._step is None or shardings != self.stat_shardings:
            self.stat_shardings = shardings
            self._step = jax.jit(self._step_fn,
                                 donate_argnums=(0,) if self._donate else (),
                                 out_shardings=self.stat_shardings)
        return jax.tree.map(
            lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
            shapes, self.stat_shardings)

    def update(self, acc, params, batch):
        """One fused step: acc + stats(batch), on device. ``acc``'s buffers
        are donated — use the return value, not the argument."""
        if self._step is None:
            raise RuntimeError(
                "sharded CalibrationEngine: call init_stats(params, batch) "
                "before update() so the stat shardings exist")
        return self._step(acc, params, batch)

    # -- sharded placement ----------------------------------------------------

    def _put_params(self, params):
        mesh = self.shard.mesh
        return jax.device_put(params, dist_sharding.shardings_of(
            dist_sharding.param_specs(params, mesh), mesh))

    def _put_batch(self, batch):
        """device_put per ``batch_specs``, caching the sharding pytree —
        calibration streams have constant shapes, so the per-batch spec
        walk would be pure hot-loop overhead."""
        key = (jax.tree.structure(batch),
               tuple(x.shape for x in jax.tree.leaves(batch)))
        if self._batch_cache is None or self._batch_cache[0] != key:
            mesh = self.shard.mesh
            self._batch_cache = (key, dist_sharding.shardings_of(
                dist_sharding.batch_specs(batch, mesh), mesh))
        return jax.device_put(batch, self._batch_cache[1])

    # -- driver ---------------------------------------------------------------

    def run(self, params, batches: Iterable, *, checkpointer=None,
            fail_hook: Optional[Callable[[int], None]] = None) -> Dict:
        """Stream ``batches`` through the fused step; returns host stats.

        Args:
          params: model parameters. In sharded mode they are device_put per
            ``param_specs`` once up front (the step then never reshards).
          batches: iterable of calibration batches (deterministic-by-index
            when resuming from a checkpoint).
          checkpointer: optional ``fault.CalibrationCheckpointer`` —
            restores the newest valid stats checkpoint (skipping the
            already-consumed stream prefix) and saves the accumulator every
            N batches (on a background thread by default — the pass never
            blocks on disk; ``run`` sync-flushes the in-flight save before
            returning). Sharded accumulators are gathered on save and
            re-placed shard-by-shard on restore (see fault.py for the
            trade-off).
          fail_hook: optional ``hook(i)`` called before batch ``i``; if it
            raises, the batch is dropped and the pass continues (the
            bounded-staleness mode of ``repro.distrib.fault`` — statistics
            carry their own sample counts, so dropped batches only
            shrink n).

        Returns:
          ``{unit.name: {stat: np-like}}`` — the summed statistics pytree,
          fetched to host (sharded accumulators are gathered).
        """
        it = iter(batches)
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("empty calibration stream") from None
        if self.shard is not None:
            params = self._put_params(params)
        acc = self.init_stats(params, first)
        start = 0
        if checkpointer is not None:
            acc, start = checkpointer.restore(
                acc, self.fingerprint, shardings=self.stat_shardings)
        n_seen = 0
        for i, batch in enumerate(itertools.chain([first], it)):
            if i < start:
                continue
            if fail_hook is not None:
                try:
                    fail_hook(i)
                except Exception:       # noqa: BLE001 — simulated host loss
                    continue
            with span("calib.batch"):
                if self.shard is not None:
                    batch = self._put_batch(batch)
                acc = self._step(acc, params, batch)
                n_seen += 1
                if checkpointer is not None:
                    checkpointer.maybe_save(acc, i + 1, self.fingerprint)
        if start == 0 and n_seen == 0:
            raise ValueError("every calibration batch failed")
        if checkpointer is not None:
            # sync-flush: the newest checkpoint is durably on disk before
            # the pass reports completion (async saves run in background)
            checkpointer.finish()
        return jax.device_get(acc)


def run_pass(model, units: List[Unit], params, batches: Iterable, *,
             phase: int = 1, plan: Optional[Dict] = None,
             checkpointer=None, mesh=None, stats_dtype="float32") -> Dict:
    """One-call convenience wrapper: build an engine and run one pass."""
    eng = CalibrationEngine(model, units, phase=phase, plan=plan, mesh=mesh,
                            stats_dtype=stats_dtype)
    return eng.run(params, batches, checkpointer=checkpointer)
