"""Host spans on the profiler's clock, and the table of names the
benchmark reads.

``span(name, **stats)`` opens a ``jax.profiler.TraceAnnotation``: while a
profiler trace is open (``jax.profiler.trace`` / ``start_trace``) the span
lands in the trace's host plane, on the same clock as the device's
programs and ops, with its small integer ``stats`` (``rid``, ``slot``,
``step``, ``tokens``, ``lanes``, ...). With no trace open it costs an
annotation's enter and exit and two clock reads. Either way the span holds
its wall time in ``.seconds`` after exit, so a stage time the program
reports is the span's own.

``NAMES`` maps every name to its kind: ``span`` (opened by ``span`` in the
program; nesting gives the parent), ``program`` (a jitted function's
module, ``jit_<function>``), ``kernel`` (a Pallas call, named after the
function that wraps it) and ``scope`` (a ``jax.named_scope`` in the
blocks, carried in the ops' metadata). Renaming any of them changes what
the benchmark finds.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

NAMES = {
    # serving: serve/frontend.py, serve/scheduler.py, serve/engine.py
    "frontend.step": "span",          # ServeFrontend.step, the iteration
    "frontend.submit": "span",        # ServeFrontend.submit; rid
    "frontend.stream": "span",        # tokens to handles, retires; lanes
    "sched.admit": "span",            # Scheduler.start; rid, slot, tokens
    "sched.advance": "span",          # Scheduler.advance with a prefill
    "engine.prefill": "span",         # first-chunk prefill dispatch; rid,
                                      # tokens, bucket
    "engine.prefill.wait": "span",    # the first token's fetch
    "engine.install": "span",         # prefix insert + slot write; slot
    "engine.decode": "span",          # decode_step, dispatch to commit;
                                      # step, lanes
    "engine.decode.wait": "span",     # the tokens' fetch in decode_step
    # pruning: core/pruner.py, core/calibrate.py, launch/prune.py
    "prune": "span",                  # the whole corp_prune call
    "prune.pass1": "span",
    "prune.rank": "span",
    "prune.pass2": "span",
    "prune.fold": "span",
    "calib.batch": "span",            # one batch of CalibrationEngine.run
    # device names the benchmark finds its programs and kernels by
    "jit__decode_impl": "program",    # ServeEngine._decode_impl
    "jit__prefill_impl": "program",   # ServeEngine._prefill_impl
    "jit_forward": "program",         # the classify benchmark's forward
    "decode_attention_splits": "kernel",   # kernels/flash_decode
    "decode_latent_attention": "kernel",   # kernels/flash_decode, MLA
    "gmm": "kernel",                  # kernels/expert_gmm (megablox)
    "flash_attention": "kernel",      # kernels/flash_attention
    "attn": "scope",                  # blocks: the attention half
    "mlp": "scope",                   # blocks: the MLP half
    "kv_write": "scope",              # attention.decode_attn: cache writes
    "mla": "scope",                   # attention: MLA's latent decode
                                      # (absorb, kernel, up-projection)
    "moe.route": "scope",             # mlp.route: the router
    "moe.experts": "scope",           # mlp: the held experts' pairs
}


def of_kind(kind: str) -> tuple:
    """The names of one kind, in table order."""
    return tuple(n for n, k in NAMES.items() if k == kind)


class span:
    """``with span("engine.decode", step=3, lanes=20) as s: ...``; then
    ``s.seconds``. ``name`` must be a ``span`` in ``NAMES``."""
    __slots__ = ("_trace", "_t0", "seconds")

    def __init__(self, name: str, **stats):
        self._trace = TraceAnnotation(name, **stats)
        self.seconds = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._trace.__enter__()
        return self

    def __exit__(self, *exc):
        self._trace.__exit__(*exc)
        self.seconds = time.perf_counter() - self._t0
