"""Fault tolerance runtime (DESIGN.md §2.3).

Three mechanisms, matched to the failure modes of a 1000+-node pruning or
training job:

1. restart loop — ``run_with_restarts`` re-enters the step function from the
   newest valid checkpoint after any failure (atomicity is guaranteed by
   repro.checkpoint; data is deterministic-by-index so the restored cursor
   reproduces the exact stream).

2. bounded-staleness calibration — CORP's statistics are *means* over
   independent samples, so a host that dies mid-pass simply contributes
   fewer samples: ``TolerantAccumulator`` drops failed batches and
   re-weights by the surviving count n. This graceful-degradation property
   is unique to one-shot closed-form compression (an optimizer-based method
   would diverge); the paper's Table 3 shows accuracy is stable down to
   100 calibration samples, which bounds the damage of losing hosts.
   (The fused ``repro.core.calibrate.CalibrationEngine`` exposes the same
   behaviour through its ``fail_hook`` argument.)

2b. resumable calibration — the engine's accumulator is a plain pytree of
   linear sums, so any stream prefix is a valid checkpoint:
   ``CalibrationCheckpointer`` persists it every N batches (atomically, via
   repro.checkpoint; serialized on a background thread by default so long
   passes never block on disk) and restores the newest valid one together
   with the batch cursor. Calibration batches are deterministic-by-index,
   so a restarted pass skips the consumed prefix and lands on identical
   statistics.

3. elastic re-mesh — ``remesh`` rebuilds the device mesh from the live
   device set; all shardings are axis-name-based (repro.distrib.sharding)
   so the job re-lowers for the surviving topology without code changes.
   Straggler mitigation falls out of the design: the only synchronization
   point is the psum inside the compiled step, and slow hosts delay but
   never deadlock; persistent stragglers are excluded at the next re-mesh.
"""
from __future__ import annotations

import logging
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint)

log = logging.getLogger("repro.fault")


class CalibrationCheckpointer:
    """Periodic, atomic checkpoints of a calibration-statistics pytree.

    Plugs into ``CalibrationEngine.run(..., checkpointer=...)``: the engine
    calls ``restore`` once (returning the newest valid accumulator and the
    number of batches it already covers), ``maybe_save`` after every batch,
    and ``finish`` after the last one. Saves reuse repro.checkpoint's
    tmp-dir-rename protocol, so a host dying mid-save can never corrupt the
    newest checkpoint.

    **Async cadence** (default): ``maybe_save`` snapshots the accumulator
    to host (a synchronous ``device_get`` — cheap, and required anyway
    before the engine donates the buffers to the next step) and hands the
    serialization + atomic rename to ``checkpoint.AsyncCheckpointer``'s
    background thread, so a long calibration pass never blocks on disk
    between batches. At most one save is in flight (the next one joins the
    previous first); ``finish`` sync-flushes so the newest checkpoint is
    durable before the pass reports completion, and re-raises any write
    error the background thread hit. A restart racing an in-flight save is
    safe by construction: the tmp-dir-rename protocol means ``restore`` in
    a new process only ever sees complete checkpoints (the interrupted save
    is simply absent — tested in tests/test_one_traversal.py). Pass
    ``async_save=False`` for strictly synchronous saves.

    Sharded accumulators (the engine's ``mesh=`` mode) are **gathered on
    save**: ``save_checkpoint`` device_gets the pytree, which assembles
    each model-sharded Sigma into one host array on disk. Trade-off: the
    on-disk format stays mesh-independent and single-file-simple, at the
    cost of one host-side full-Sigma materialisation per save (bounded: one
    statistic tree, not one per unit group) — per-shard saves would avoid
    that peak but tie the checkpoint to the exact device layout. Restore
    re-places the gathered arrays shard-by-shard via the engine's
    ``stat_shardings``, so the resumed donated step starts from a correctly
    sharded accumulator. Despite the mesh-independent format, the engine's
    fingerprint *includes* the mesh layout: a checkpoint written under a
    different mesh is rejected (fresh start) because shard-local
    accumulation order differs and bitwise resume could not be guaranteed.
    """

    def __init__(self, ckpt_dir: str, every: int = 8,
                 async_save: bool = True, keep: int = 3):
        assert every >= 1, "checkpoint interval must be >= 1 batch"
        self.ckpt_dir = ckpt_dir
        self.every = every
        self._async = AsyncCheckpointer(ckpt_dir, keep=keep) \
            if async_save else None

    def restore(self, like, fingerprint: str = "", shardings=None):
        """-> (accumulator, n_batches_consumed); (like, 0) when fresh.

        fingerprint: the engine's configuration hash (phase + unit set +
        pass-2 plan + mesh layout when sharded). A checkpoint written under
        a different fingerprint — a reused directory from another
        sparsity/plan/model run, or the same pass on a different mesh — is
        ignored (fresh start) instead of silently resuming statistics that
        do not belong to this pass. Note the calibration *stream* is not
        fingerprinted: resuming assumes deterministic-by-index batches, as
        everywhere else in this runtime.

        shardings: optional NamedSharding pytree matching ``like`` (the
        engine's ``stat_shardings``); restored arrays are device_put with
        it so a sharded pass resumes with a correctly placed, donatable
        accumulator.
        """
        import json
        import os
        self.finish()          # never read under our own in-flight save
        last = latest_step(self.ckpt_dir)
        if last is None:
            return like, 0
        # check identity from the manifest BEFORE unflattening — a foreign
        # checkpoint may not even have this accumulator's tree structure
        man = os.path.join(self.ckpt_dir, f"step_{last:08d}",
                           "manifest.json")
        saved_fp = json.load(open(man)).get("extra", {}) \
            .get("fingerprint", "")
        if fingerprint and saved_fp != fingerprint:
            log.warning("calibration checkpoint in %s was written for a "
                        "different configuration (fingerprint %r != %r); "
                        "ignoring it and starting fresh", self.ckpt_dir,
                        saved_fp, fingerprint)
            return like, 0
        acc, _extra = restore_checkpoint(self.ckpt_dir, last, like)
        log.info("resumed calibration stats at batch %d", last)
        # back onto device so the engine can donate the buffers
        if shardings is not None:
            return jax.device_put(acc, shardings), last
        return jax.tree.map(jnp.asarray, acc), last

    def maybe_save(self, acc, n_batches: int, fingerprint: str = "",
                   force: bool = False):
        if force or n_batches % self.every == 0:
            extra = {"n_batches": n_batches, "fingerprint": fingerprint}
            if self._async is not None:
                # snapshot-to-host now (safe against buffer donation),
                # write + atomic rename on the background thread
                self._async.save(n_batches, acc, extra)
            else:
                from repro.checkpoint import save_checkpoint
                save_checkpoint(self.ckpt_dir, n_batches, acc, extra)

    def finish(self):
        """Sync-flush: block until the in-flight background save (if any)
        is durably on disk; re-raises its error. No-op in sync mode."""
        if self._async is not None:
            self._async.wait()


def run_with_restarts(make_state, step_fn, *, ckpt_dir: str,
                      total_steps: int, save_every: int,
                      max_restarts: int = 10, save_fn=None):
    """Generic restartable loop.

    make_state() -> state pytree (fresh);
    step_fn(state, step) -> state;
    save_fn(state, step) defaults to repro.checkpoint.save_checkpoint.
    """
    from repro.checkpoint import save_checkpoint
    save_fn = save_fn or (lambda st, s: save_checkpoint(ckpt_dir, s, st))
    restarts = 0
    while True:
        state = make_state()
        start = 0
        last = latest_step(ckpt_dir)
        if last is not None:
            state, _ = restore_checkpoint(ckpt_dir, last, state)
            start = last
            log.info("restored step %d", last)
        try:
            for step in range(start, total_steps):
                state = step_fn(state, step)
                if (step + 1) % save_every == 0 or step + 1 == total_steps:
                    save_fn(state, step + 1)
            return state
        except Exception as e:           # noqa: BLE001 — restart anything
            restarts += 1
            log.warning("step failed (%s); restart %d/%d", e, restarts,
                        max_restarts)
            if restarts > max_restarts:
                raise


class TolerantAccumulator:
    """Bounded-staleness statistics accumulation for CORP calibration.

    Accumulates linear statistics batch-by-batch; a batch whose computation
    raises (simulating a lost host / preempted slice) is dropped and the
    final statistics are re-weighted by the surviving sample count — the
    estimator stays unbiased because calibration batches are i.i.d.
    """

    def __init__(self, step_fn: Callable, params,
                 fail_hook: Optional[Callable[[int], None]] = None):
        self.step_fn = jax.jit(step_fn)
        self.params = params
        self.fail_hook = fail_hook
        self.total = None
        self.n_ok = 0
        self.n_failed = 0

    def run(self, batches: Iterable):
        from repro.core.stats import tree_add
        for i, batch in enumerate(batches):
            try:
                if self.fail_hook is not None:
                    self.fail_hook(i)     # may raise to simulate failure
                out = self.step_fn(self.params, batch)
            except Exception:             # noqa: BLE001
                self.n_failed += 1
                continue
            self.total = tree_add(self.total, out)
            self.n_ok += 1
        assert self.total is not None, "every calibration batch failed"
        return jax.device_get(self.total)


def remesh(shape_hint=None, axis_names=("data", "model")):
    """Build the largest mesh the *live* device set supports (elastic)."""
    devs = jax.devices()
    n = len(devs)
    if shape_hint is not None and int(np.prod(shape_hint)) <= n:
        shape = shape_hint
    else:
        # fall back: squarest 2-axis factorization of n
        a = int(np.sqrt(n))
        while n % a:
            a -= 1
        shape = (n // a, a)
    from repro.launch.mesh import auto_mesh
    return auto_mesh(shape, axis_names[-len(shape):])
