"""Pallas TPU kernels for the CORP framework.

Each kernel package provides:
  <name>.py - pl.pallas_call kernel with explicit BlockSpec VMEM tiling
  ops.py    - jit'd public wrapper with backend dispatch (pallas on TPU,
              memory-sane XLA implementation elsewhere, interpret for tests)
  ref.py    - pure-jnp oracle used by the test suite

Kernels:
  flash_attention - attention over whole sequences (calibration forward,
                    prefill, training, the ViT forward) — the dominant
                    non-GEMM compute. Bidirectional attention whose
                    sequences fit VMEM whole (a ViT's 197 tokens) runs one
                    program per block of sequences with every head in
                    lanes; causal, windowed or long attention runs
                    blockwise online softmax over (q, kv) tiles.
  gram            - streaming second-moment (X^T X) accumulation — CORP's
                    calibration statistics hot-spot (Alg. 3/5 inputs).
  wkv6            - RWKV-6 chunked linear-attention recurrence (rwkv6-3b arch).
  flash_decode    - split-KV single-token decode attention (FlashDecoding) —
                    the memory-bound serving hot path the paper's pruning
                    targets; and MLA's absorbed decode over the latent rows.
  expert_gmm      - grouped matmul of the held experts' (token, expert)
                    pairs (megablox ``gmm`` on TPU; ops.py + ref.py only).
"""
import collections
import contextlib
import threading

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

# (op, impl) -> how many times a dispatcher resolved ``op`` to ``impl``. The
# ops resolve while jax traces them, so this records what every compiled
# program holds: ``chip_smoke.py`` reads it to prove that the main path ran
# its Pallas kernels on the chip.
RESOLVED: collections.Counter = collections.Counter()


def note_impl(op: str, impl: str, n: int = 1) -> str:
    """Record that ``op`` resolved to ``impl`` (``n`` times: one trace of a
    scanned layer stands for every layer of its stack); returns ``impl``."""
    RESOLVED[(op, impl)] += n
    return impl


# The Pallas TPU lowering refuses a kernel inside a program that spans more
# than one device unless a shard_map makes it a per-device program. A
# sharded step traces its body under ``kernel_mesh(mesh)``; the dispatchers
# then wrap their kernel with ``per_device``.
_CTX = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace the kernels called inside as per-device programs on ``mesh``
    (``None``: plain calls, e.g. inside an enclosing shard_map)."""
    prev = getattr(_CTX, "mesh", None)
    _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.mesh = prev


def per_device(fn, args, dims, out_dims=None):
    """Call ``fn(*args)`` once per device of the active kernel mesh.

    ``dims[i]`` names how each dim of ``args[i]`` may split: ``"batch"``
    over every mesh axis but ``model``, ``"model"`` over ``model``, ``None``
    never. A name splits only if its axis size divides every dim that
    carries it (so q heads and kv heads split together or not at all).
    ``out_dims`` names the output dims the same way (a list of such tuples
    for a tuple of outputs); ``None`` means a replicated output. Without
    an active mesh this is ``fn(*args)``.
    """
    mesh = getattr(_CTX, "mesh", None)
    if mesh is None or mesh.size == 1:
        return fn(*args)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = tuple(a for a in mesh.axis_names if a != "model")
    axes = {"batch": batch, "model": ("model",) if "model" in sizes else ()}
    size = {k: int(np.prod([sizes[a] for a in v])) for k, v in axes.items()}
    split = {k for k in axes if size[k] > 1 and all(
        n % size[k] == 0 for x, d in zip(args, dims)
        for n, name in zip(x.shape, d) if name == k)}

    def spec(d):
        return P(*(axes[name] if name in split else None for name in d))

    outs = P() if out_dims is None else (
        tuple(spec(d) for d in out_dims) if isinstance(out_dims, list)
        else spec(out_dims))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(d) for d in dims),
                         out_specs=outs, check_vma=False)(*args)
