"""Sharding rules for params and activations.

Logical design (DESIGN.md §2.1):
  * mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model') multi-pod.
  * tensor parallelism over 'model' (Megatron column/row split; expert
    parallelism for MoE; head parallelism for attention where divisible).
  * optional FSDP (ZeRO-3) over ('pod','data') on a second dim for large
    models — params/optimizer state are all-gathered per scanned layer.
  * activations: batch over ('pod','data'); sequence-parallel residual over
    'model' when the shape allows (Megatron-SP, GSPMD inserts the gathers).

Everything is expressed against axis *names*, so re-meshing (elastic scaling)
re-lowers without code changes.

``constrain(x, kind)`` applies a with_sharding_constraint according to the
active activation policy (a context variable set by the launchers) and is a
no-op outside any policy — model code stays mesh-agnostic.

Calibration statistics get their own sharding contract (``stats_specs`` +
``CalibSharding``): per-unit second-moment/Gram blocks are column-sharded
over the model axis so a calibration pass never materialises a replicated
full Sigma on any device (see docs/calibration.md and
``repro.core.calibrate.CalibrationEngine``).

The serving slot cache likewise has its own contract
(``repro.serve.sharding``: ``slot_specs`` + ``ServeSharding``), which
composes with this module — a sharded ``ServeEngine`` places its params
via ``param_specs``/``shardings_of`` on the same mesh its cache splits
over. Both spec builders share the dict-mesh testability idiom pioneered
by ``stats_specs`` below.
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


# ---------------------------------------------------------------------------
# activation policy
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def activation_policy(rules: dict, mesh=None):
    """rules: {'residual': PartitionSpec | None, 'logits': ...}.

    ``mesh`` must be the concrete mesh the step lowers under: the abstract
    mesh is EMPTY inside ``with mesh:`` (verified), so divisibility checks
    need the real axis sizes — otherwise non-divisible constraints silently
    lower as padded shardings.
    """
    prev = getattr(_STATE, "rules", None)
    prev_mesh = getattr(_STATE, "mesh", None)
    _STATE.rules = rules
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.rules = prev
        _STATE.mesh = prev_mesh


def _spec_fits(x, spec) -> bool:
    mesh = getattr(_STATE, "mesh", None)
    if mesh is None:
        return False
    try:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    except Exception:
        return False
    for dim, names in enumerate(spec):
        if names is None:
            continue
        group = names if isinstance(names, tuple) else (names,)
        total = int(np.prod([sizes.get(n, 1) for n in group]))
        if total > 1 and (dim >= x.ndim or x.shape[dim] % total):
            return False
    return True


def constrain(x, kind: str):
    """Apply the active activation policy's sharding constraint to ``x``.

    Args:
      x: activation array (any rank).
      kind: rule key — 'residual', 'logits', 'mamba_inner', 'attn_qkv'
        (see ``make_activation_rules``).

    Returns ``x`` unchanged outside any policy, when the policy has no rule
    for ``kind``, or when the spec doesn't divide ``x``'s shape on the
    active mesh (never pads); otherwise ``with_sharding_constraint(x,
    spec)``. Model code calls this unconditionally and stays mesh-agnostic.
    """
    rules = getattr(_STATE, "rules", None)
    if not rules:
        return x
    spec = rules.get(kind)
    if spec is None or not _spec_fits(x, spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_qkv(q, k, v):
    """Head-shard q/k/v ALL-or-nothing: applying the layout to q alone when
    kv heads don't divide the model axis (GQA with few kv heads) forces a
    reshard inside attention — worse than no constraint (§Perf D2 note)."""
    rules = getattr(_STATE, "rules", None)
    if not rules:
        return q, k, v
    spec = rules.get("attn_qkv")
    if spec is None or not (_spec_fits(q, spec) and _spec_fits(k, spec)
                            and _spec_fits(v, spec)):
        return q, k, v
    c = jax.lax.with_sharding_constraint
    return c(q, spec), c(k, spec), c(v, spec)


def make_activation_rules(batch_axes=("data",), model_axis="model",
                          seq_shard=True):
    """Standard activation-sharding rule set for ``activation_policy``.

    Args:
      batch_axes: mesh axes the batch dim shards over (tuple).
      model_axis: tensor-parallel axis name.
      seq_shard: sequence-parallel residual (Megatron-SP) when True.

    Returns ``{kind: PartitionSpec}`` for 'residual' (B, T, D),
    'logits' (B, T, V), 'mamba_inner' (B, T, d_inner) and
    'attn_qkv' (B, T, H, d) — the keys ``constrain``/``constrain_qkv``
    look up.
    """
    resid = P(batch_axes, model_axis if seq_shard else None, None)
    return {
        "residual": resid,
        "logits": P(batch_axes, None, model_axis),
        # mamba inner activations: channel-sharded over 'model', sequence-
        # unsharded — the per-channel recurrence needs zero cross-chip
        # traffic (§Perf J1). A batch-over-(data x model) variant was tried
        # and REFUTED: the residual reshard at every mamba/attention boundary
        # cost far more than it saved (EXPERIMENTS.md §Perf J4).
        "mamba_inner": P(batch_axes, None, model_axis),
        # attention q/k/v (B,T,H,d): heads over 'model', sequence gathered —
        # with a sequence-sharded residual the layout change lowers to an
        # all-to-all (constant per-chip bytes) instead of K/V all-gathers
        # (§Perf iteration D2)
        "attn_qkv": P(batch_axes, None, model_axis, None),
    }


# ---------------------------------------------------------------------------
# parameter sharding
# ---------------------------------------------------------------------------

def _divisible(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


def _spec_for(path: str, shape, model_size: int, fsdp_axes, fsdp_size: int,
              scanned: bool):
    """Return a PartitionSpec for one parameter array."""
    dims = list(shape)
    off = 1 if scanned else 0   # leading scan/reps axis is never sharded
    body = dims[off:]
    spec = [None] * len(dims)

    def assign(i, name):
        spec[off + i] = name

    leaf = path.rsplit("/", 1)[-1]

    model_dim = None
    # priority: expert axis > head axis > wide/output axis > input axis
    if leaf in ("wg", "wu", "wd") and len(body) == 3:        # MoE (E, D, F)
        if _divisible(body[0], model_size):
            model_dim = 0
    elif leaf in ("wq", "wk", "wv") and len(body) == 3:      # (D, H, dq)
        if _divisible(body[1], model_size):
            model_dim = 1
        elif _divisible(body[0], model_size):
            model_dim = 0
    elif leaf == "wo" and len(body) == 3:                    # (H, dv, D)
        if _divisible(body[0], model_size):
            model_dim = 0
        elif _divisible(body[2], model_size):
            model_dim = 2
    elif leaf in ("w_uq_nope", "w_uq_rope", "w_uk_nope", "w_uv") \
            and len(body) == 3:                              # (r, H, d)
        if _divisible(body[1], model_size):
            model_dim = 1
    elif leaf in ("embed", "head") and len(body) == 2:
        # vocab-sharded (vocab padded to a multiple of the model axis)
        vdim = 0 if body[0] >= body[1] else 1
        if _divisible(body[vdim], model_size):
            model_dim = vdim
    elif leaf == "router":
        model_dim = None
    elif len(body) == 2:
        # generic linear: shard the wider dim on 'model'
        cand = 0 if body[0] >= body[1] else 1
        if _divisible(body[cand], model_size):
            model_dim = cand
        elif _divisible(body[1 - cand], model_size):
            model_dim = 1 - cand
    elif len(body) == 1:
        model_dim = None

    if model_dim is not None:
        assign(model_dim, "model")

    if fsdp_axes and fsdp_size > 1:
        for i, d in enumerate(body):
            if spec[off + i] is None and len(body) >= 2 \
                    and _divisible(d, fsdp_size):
                assign(i, fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0])
                break
    return P(*spec)


def param_specs(params, mesh: Mesh, *, fsdp: bool = False):
    """PartitionSpec pytree matching ``params``.

    Args:
      params: parameter pytree (real arrays or ``jax.eval_shape`` output —
        only ``.shape`` is inspected, so abstract trees work).
      mesh: target mesh; axis sizes gate divisibility (a dim that doesn't
        divide the 'model' axis is left unsharded rather than padded).
      fsdp: additionally shard one remaining dim of every >=2-D param over
        ('pod','data') — ZeRO-3 style parameter sharding.

    Returns:
      A pytree of ``PartitionSpec`` with the same structure as ``params``;
      feed it to ``shardings_of`` for ``NamedSharding`` leaves.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)
    fsdp_axes = tuple(a for a in ("pod", "data") if a in sizes) if fsdp else ()
    fsdp_size = int(np.prod([sizes[a] for a in fsdp_axes])) if fsdp_axes else 1

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        scanned = "/p" in path and any(
            seg.startswith("p") and seg[1:].isdigit()
            for seg in path.split("/"))
        specs.append(_spec_for(path, leaf.shape, model_size, fsdp_axes,
                               fsdp_size, scanned))
    return jax.tree_util.tree_unflatten(treedef, specs)


def shardings_of(specs, mesh: Mesh):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh`` (specs are
    treated as leaves, so nested dict/list structures pass through)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def init_sharded(init, key, mesh: Mesh, *, fsdp: bool = False):
    """``init(key)`` with every leaf born on its ``param_specs`` shards.

    The init runs under jit with ``out_shardings``, so no device ever holds
    the whole parameter tree (building on device 0 and ``device_put``-ing
    afterwards cannot fit a model larger than one device)."""
    specs = param_specs(jax.eval_shape(init, key), mesh, fsdp=fsdp)
    return jax.jit(init, out_shardings=shardings_of(specs, mesh))(key)


def batch_specs(batch_tree, mesh: Mesh):
    """Shard every batch array's leading (batch) dim over ('pod','data').

    Arrays whose leading dim doesn't divide the data-parallel world size are
    left replicated (never padded). Returns a PartitionSpec pytree matching
    ``batch_tree``.
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    def f(x):
        spec = [None] * x.ndim
        if x.ndim >= 1 and x.shape[0] % int(
                np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                         for a in axes])) == 0:
            spec[0] = axes if len(axes) > 1 else axes[0]
        return P(*spec)
    return jax.tree.map(f, batch_tree)


# ---------------------------------------------------------------------------
# calibration-statistics sharding
# ---------------------------------------------------------------------------

class CalibSharding(NamedTuple):
    """How a calibration pass is laid out on a mesh.

    mesh: the device mesh the fused statistics step runs under.
    model_axis: mesh axis partitioning per-unit covariance/Gram columns.
    batch_axes: mesh axes the calibration batch is sharded over; per-batch
      partial sums reduce over these via psum inside the compiled step.
    """
    mesh: Mesh
    model_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("pod", "data")

    @property
    def sizes(self):
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def model_size(self) -> int:
        return self.sizes.get(self.model_axis, 1)

    @property
    def present_batch_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.batch_axes if a in self.sizes)


# Stat leaves that stay replicated: scalar-ish bookkeeping whose size never
# grows with the unit width (sample counts, pruned-tail energies, and the
# one-traversal engine's per-group Frobenius totals).
_REPLICATED_STATS = frozenset({"n", "t2", "t2_tot"})


def stats_specs(stats, mesh, *, model_axis: str = "model"):
    """PartitionSpecs for a calibration-statistics pytree.

    Every per-unit statistic leaf whose trailing dim divides the model-axis
    size is sharded on that trailing dim over ``model_axis`` — for a second
    moment ``s2: (F, F)`` that is column sharding, so each device holds an
    (F, F/m) slab and no device ever allocates a replicated full Sigma.
    Sample counts ``n`` and pruned-tail energies ``t2`` stay replicated
    (they are O(1) per unit). Leading stack/expert/group dims are never
    sharded.

    Args:
      stats: statistics pytree (arrays or ``jax.eval_shape`` structs; only
        ``.shape``/``.ndim`` are inspected). Leaf *names* (the innermost
        dict key: 's2', 's1', 'na', 'rank', 'G', 'h', 'n', 't2', and the
        speculative one-traversal leaves 'Gc', 'Hfull', 'hfull', 't2_tot')
        choose the rule; unknown wide leaves get the default
        trailing-dim-over-model treatment when divisible.
      mesh: a ``jax.sharding.Mesh`` — or a plain ``{axis: size}`` dict,
        which makes the rule testable without devices.
      model_axis: mesh axis name to shard over.

    Returns:
      PartitionSpec pytree matching ``stats``.

    >>> tree = {"blk/mlp": {"s2": np.zeros((3, 8, 8)), "s1": np.zeros((3, 8)),
    ...                     "n": np.zeros((3,))}}
    >>> specs = stats_specs(tree, {"data": 2, "model": 4})
    >>> specs["blk/mlp"]["s2"] == P(None, None, "model")
    True
    >>> specs["blk/mlp"]["s1"] == P(None, "model")
    True
    >>> specs["blk/mlp"]["n"] == P()     # counts stay replicated
    True
    >>> stats_specs(tree, {"data": 2, "model": 3})["blk/mlp"]["s2"] == P()
    True
    """
    sizes = mesh if isinstance(mesh, dict) else \
        dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get(model_axis, 1)

    flat = jax.tree_util.tree_flatten_with_path(stats)[0]
    treedef = jax.tree_util.tree_structure(stats)
    specs = []
    for kp, leaf in flat:
        name = str(getattr(kp[-1], "key", getattr(kp[-1], "idx", kp[-1])))
        if (m <= 1 or leaf.ndim == 0 or name in _REPLICATED_STATS
                or leaf.shape[-1] % m or leaf.shape[-1] < m):
            specs.append(P())
        else:
            specs.append(P(*([None] * (leaf.ndim - 1)), model_axis))
    return jax.tree_util.tree_unflatten(treedef, specs)

