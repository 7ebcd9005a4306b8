"""Mesh sharding for the serving slot cache.

The serving half of the `distrib.sharding` story (docs/serving.md
"Mesh-sharded serving"): the engine's shared decode step runs under pjit
on a ``(data, model)`` mesh with every slot-cache leaf explicitly placed —
the structurally-inferred slot axis (``cache._infer_batch_axes``) becomes
the data axis, and each *payload* leaf shards over the model axis on the
dim its ``cache_contract`` family parallelises:

  contract    leaf                  model-sharded dim
  ---------   -------------------   ------------------------------
  kv          k / v                 kv heads        (..., S, Hkv*d)
  kv (MLA)    lat                   latent lanes    (..., T, r + rope)
  recurrent   wkv                   rwkv heads      (..., H, N, N)
  recurrent   ssm                   ssm channels    (..., d_inner, N)
  recurrent   conv / shift          conv channels   (..., K, d_inner)
  encdec      k_mem / v_mem         cross heads     (..., M, Hkv*d)
  (all)       pos / abs_pos         replicated bookkeeping

Dims are counted FROM THE END of the shape, so leading stack axes
(scanned segments prepend ``(reps, ...)``, enc-dec decoders prepend
``(n_layers, ...)``) shift nothing. K/V leaves fold their kv heads into
the last dim (the layout ``flash_decode`` reads in place), so their split
must fall between heads: ``kv_heads`` must divide by the model-axis size.
A payload dim that does not divide the model-axis size is never padded:
the whole config is refused with
the shared ``shard_ineligible`` message (``serve/errors.py``), which is
exactly the eligibility matrix ``tests/test_serve_zoo.py`` pins — GQA
configs whose reduced form collapses to one kv head cannot model-shard.

Like ``distrib.sharding.stats_specs``, ``slot_specs`` accepts a plain
``{axis: size}`` dict in place of a mesh so the placement rules are
testable without devices.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.serve import errors

# Payload leaves whose model dim folds ``kv_heads`` heads into lanes.
HEAD_FOLDED_LEAVES = frozenset({"k", "v", "k_mem", "v_mem"})

# Bookkeeping leaves that never shard over the model axis: per-slot valid
# lengths / ring positions are O(1) per slot and every device's decode
# mask consumes the whole vector.
REPLICATED_SLOT_LEAVES = frozenset({"pos", "abs_pos"})

# Payload leaves: the model-axis dim, counted from the end of the shape.
MODEL_DIM_FROM_END = {
    "k": 1, "v": 1,            # attn KV rows   (..., S, Hkv*d)
    "k_mem": 1, "v_mem": 1,    # enc-dec cross  (..., M, Hkv*d)
    "wkv": 3,                  # rwkv6 state    (..., H, N, N)
    "ssm": 2,                  # mamba state    (..., d_inner, N)
    "conv": 1,                 # mamba conv     (..., K, d_inner)
    "shift": 1,                # rwkv shifts    (..., D)
    "lat": 1,                  # MLA latent     (..., T, rank + rope)
}


class ServeSharding(NamedTuple):
    """How a serving engine is laid out on a mesh (the serve-side analogue
    of ``distrib.sharding.CalibSharding``).

    mesh: the device mesh the shared decode step runs under.
    data_axis: mesh axis the slot (batch) dim shards over.
    model_axis: mesh axis the cache payload dims shard over.
    """
    mesh: Mesh
    data_axis: str = "data"
    model_axis: str = "model"

    @property
    def sizes(self) -> dict:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def data_size(self) -> int:
        return self.sizes.get(self.data_axis, 1)

    @property
    def model_size(self) -> int:
        return self.sizes.get(self.model_axis, 1)


def _mesh_sizes(mesh) -> dict:
    return mesh if isinstance(mesh, dict) else \
        dict(zip(mesh.axis_names, mesh.devices.shape))


def _leaf_name(kp) -> str:
    return str(getattr(kp[-1], "key", getattr(kp[-1], "idx", kp[-1])))


def slot_specs(template, batch_axes, mesh, *, kv_heads=None,
               data_axis: str = "data", model_axis: str = "model",
               name: str = "slot-cache"):
    """PartitionSpecs for a slot-cache pytree.

    Args:
      template: cache pytree (arrays or ``jax.eval_shape`` structs; only
        ``.shape``/``.ndim`` are inspected). Leaf *names* (the innermost
        dict key) choose the rule — see ``MODEL_DIM_FROM_END`` /
        ``REPLICATED_SLOT_LEAVES``; unknown leaves stay model-replicated.
      batch_axes: per-leaf slot-axis index pytree
        (``SlotCache.batch_axes``). The slot dim shards over ``data_axis``
        when it divides that axis size (a batch-1 local template therefore
        comes out data-replicated, which is what the scatter-admit needs).
      mesh: a ``jax.sharding.Mesh`` — or a plain ``{axis: size}`` dict,
        which makes the rules testable without devices.
      kv_heads: the kv heads folded into the K/V leaves' last dim
        (``HEAD_FOLDED_LEAVES``); needed to split them over a model axis.
      name: config name for the ``shard_ineligible`` refusal.

    Raises:
      ValueError(``errors.msg("shard_ineligible", ...)``) when any payload
      leaf's model dim does not divide the model-axis size (for a
      head-folded leaf: when ``kv_heads`` does not) — sharding is
      all-or-nothing per config, never padded.

    >>> tmpl = {"k": np.zeros((4, 16, 16)), "v": np.zeros((4, 16, 16)),
    ...         "pos": np.zeros((4,), np.int32)}
    >>> axes = {"k": 0, "v": 0, "pos": 0}
    >>> sp = slot_specs(tmpl, axes, {"data": 2, "model": 2}, kv_heads=2)
    >>> sp["k"] == P("data", None, "model")
    True
    >>> sp["pos"] == P("data")        # bookkeeping: slot axis only
    True
    >>> local = slot_specs({"k": np.zeros((1, 16, 16))}, {"k": 0},
    ...                    {"data": 2, "model": 2}, kv_heads=2)
    >>> local["k"] == P(None, None, "model")   # batch-1: no data dim
    True
    >>> try:                          # Hkv=2 cannot split a 4-way axis
    ...     slot_specs(tmpl, axes, {"model": 4}, kv_heads=2)
    ... except ValueError:
    ...     print("refused")
    refused
    """
    sizes = _mesh_sizes(mesh)
    d = sizes.get(data_axis, 1)
    m = sizes.get(model_axis, 1)

    flat = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    axes_flat = jax.tree_util.tree_leaves(batch_axes)
    specs = []
    for (kp, leaf), slot_ax in zip(flat, axes_flat):
        leaf_name = _leaf_name(kp)
        spec = [None] * leaf.ndim
        if d > 1 and leaf.shape[slot_ax] % d == 0:
            spec[slot_ax] = data_axis
        if m > 1 and leaf_name in MODEL_DIM_FROM_END:
            md = leaf.ndim - MODEL_DIM_FROM_END[leaf_name]
            folded = leaf_name in HEAD_FOLDED_LEAVES
            if folded and kv_heads is None:
                raise TypeError(f"leaf {leaf_name!r} folds kv heads into "
                                "lanes: slot_specs needs kv_heads=")
            if md < 0 or md == slot_ax or \
                    (kv_heads if folded else leaf.shape[md]) % m:
                raise ValueError(errors.msg("shard_ineligible", name=name,
                                            leaf=leaf_name, m=m))
            spec[md] = model_axis
        specs.append(P(*spec))
    return jax.tree_util.tree_unflatten(treedef, specs)


def device_bytes_estimate(template, specs, mesh) -> int:
    """Analytic per-device bytes of a sharded cache (no allocation).

    Divides every leaf's total bytes by the product of the mesh-axis sizes
    its spec shards over — exact when every sharded dim divides (which
    ``slot_specs`` guarantees). Works on ``jax.eval_shape`` templates, so
    a full-scale (671B-class) config's footprint is computable on a laptop.

    >>> tmpl = {"k": np.zeros((4, 16, 64), np.float32)}
    >>> sp = {"k": P("data", None, "model")}
    >>> device_bytes_estimate(tmpl, sp, {"data": 2, "model": 4})
    2048
    """
    sizes = _mesh_sizes(mesh)
    leaves = jax.tree_util.tree_leaves(template)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    total = 0
    for leaf, spec in zip(leaves, spec_leaves):
        nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        denom = 1
        for names in spec:
            if names is None:
                continue
            group = names if isinstance(names, tuple) else (names,)
            denom *= int(np.prod([sizes.get(a, 1) for a in group]))
        total += nbytes // denom
    return int(total)
