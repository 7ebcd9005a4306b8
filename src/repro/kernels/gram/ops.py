"""Public gram ops with backend dispatch (env ``REPRO_GRAM_IMPL`` overrides).

Dispatch policy (the calibration hot path calls this for every second-moment
reduction, see ``repro.core.stats._moments``):

  * TPU backend  -> the Pallas streaming kernel; arbitrary (N, F) shapes are
    handled by zero-padding inside ``gram.gram``.
  * anything else (CPU/GPU) -> the pure-jnp reference — XLA's plain matmul
    is the right lowering there, and it keeps interpret-mode Pallas off the
    production path.
  * ``REPRO_GRAM_IMPL`` in {"ref", "pallas", "interpret"} forces a backend
    (interpret = Pallas interpreter, used by the CPU test suite).

Tile sizes: ``bf``/``bn`` default to None = the analytic roofline autotuner
(``repro.kernels.gram.autotune``, cached per shape/dtype); pass ints to pin,
or set ``REPRO_GRAM_TILES=BF,BN`` to pin globally (what ``--gram-tiles`` in
launch.prune sets). Inputs stream in their own dtype — pass bf16 activations
to halve HBM traffic; accumulation is fp32 in all backends.

Three entry points:

  ``gram(x)``                 full (F, F) second moment of one host's X.
  ``gram_cross(x, y)``        rectangular X^T Y — the per-shard slab.
  ``gram_sharded(x, mesh)``   shard_map-routed gram whose (F, F) output is
                              column-sharded over the mesh's model axis; each
                              shard runs the kernel on its LOCAL (N_local,
                              F/m) column tile (zero-padding included), so no
                              device ever materialises — or pads — a full
                              Sigma. Batch-axis contributions are psum-reduced
                              inside the shard_map. Tiles autotune on the
                              local shapes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import kernel_mesh, note_impl, per_device
from repro.kernels.gram import ref as _ref
from repro.kernels.gram.gram import gram as _pallas_gram
from repro.kernels.gram.gram import gram_cross as _pallas_gram_cross


def _resolve_impl() -> str:
    impl = os.environ.get("REPRO_GRAM_IMPL", "")
    if impl:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _env_tiles(bf, bn):
    """Apply the ``REPRO_GRAM_TILES=BF,BN`` global pin to unset tile args
    (explicit arguments win; unset with no env falls through to the
    autotuner inside the kernel)."""
    env = os.environ.get("REPRO_GRAM_TILES", "")
    if env:
        ebf, ebn = (int(v) for v in env.split(","))
        bf, bn = bf or ebf, bn or ebn
    return bf, bn


def gram(x, impl=None, *, bf=None, bn=None):
    """x: (N, F) -> {'s2': (F, F), 's1': (F,)} in fp32. Any (N, F), any
    float dtype (bf16 tiles stream at half the HBM traffic)."""
    impl = note_impl("gram", impl or _resolve_impl())
    if impl == "ref":
        return _ref.gram(x)
    bf, bn = _env_tiles(bf, bn)
    return per_device(lambda x: _pallas_gram(
        x, bf=bf, bn=bn, interpret=(impl == "interpret")), (x,),
        ((None, None),))


def gram_cross(x, y, impl=None, *, bf=None, bn=None):
    """x: (N, Fx), y: (N, Fy) -> {'s2': (Fx, Fy) X^T Y, 's1': (Fy,) column
    sums of Y} in fp32. The building block of the sharded gram: y is one
    shard's local column block of x."""
    impl = note_impl("gram", impl or _resolve_impl())
    if impl == "ref":
        return _ref.gram_cross(x, y)
    bf, bn = _env_tiles(bf, bn)
    return per_device(lambda x, y: _pallas_gram_cross(
        x, y, bf=bf, bn=bn, interpret=(impl == "interpret")), (x, y),
        ((None, None), (None, None)))


def gram_sharded(x, mesh, *, model_axis="model", batch_axes=("data",),
                 impl=None, bf=None, bn=None):
    """Model-sharded gram: x (..., N, F) -> column-sharded {'s2', 's1'}.

    Args:
      x: (..., N, F) activations, any float dtype — bf16 streams each
        shard's tiles at half the HBM traffic (accumulation stays fp32
        inside the kernel). Leading dims (e.g. a scanned layer stack) are
        vmapped; N (tokens) must be divisible by the product of the mesh
        ``batch_axes`` sizes and F by the ``model_axis`` size.
      mesh: the ``jax.sharding.Mesh`` to shard over.
      model_axis: mesh axis name that partitions Sigma's columns.
      batch_axes: mesh axes the token rows are sharded over; their partial
        sums are psum-reduced inside the shard_map.
      bf, bn: kernel tiles; None = autotune on each shard's LOCAL
        (N_local, F/m) tile shape.

    Returns:
      {'s2': (..., F, F) fp32 with spec P(..., None, model_axis),
       's1': (..., F)  fp32 with spec P(..., model_axis)}.

    Each shard slices its own F/m column block and runs ``gram_cross`` on
    the local (N_local, F/m) tile — kernel zero-padding therefore happens on
    local tiles, and per-device Sigma memory is F*F/m, never F*F.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in batch_axes if a in sizes)
    m = sizes.get(model_axis, 1)
    d = int(np.prod([sizes[a] for a in batch_axes])) if batch_axes else 1
    lead = x.ndim - 2
    N, F = x.shape[-2], x.shape[-1]
    assert m > 1, "gram_sharded needs a >1-way model axis; use gram()"
    assert F % m == 0, f"F={F} not divisible by {model_axis}={m}"
    assert N % d == 0, f"N={N} not divisible by batch axes {batch_axes}={d}"
    fl = F // m
    row_spec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    lead_spec = (None,) * lead

    def local(xl):
        # keep the streaming dtype: the kernel casts tiles to fp32 in VMEM,
        # so a bf16 xl halves this shard's HBM reads
        j = jax.lax.axis_index(model_axis)
        xj = jax.lax.dynamic_slice_in_dim(xl, j * fl, fl, axis=xl.ndim - 1)

        fn = lambda a, b: gram_cross(a, b, impl=impl, bf=bf, bn=bn)
        for _ in range(lead):
            fn = jax.vmap(fn)
        with kernel_mesh(None):        # already one program per device
            out = fn(xl, xj)
        if batch_axes:
            out = jax.lax.psum(out, batch_axes)
        return out

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=P(*lead_spec, row_spec, None),
        out_specs={"s2": P(*lead_spec, None, model_axis),
                   "s1": P(*lead_spec, model_axis)},
        check_vma=False)(x)
