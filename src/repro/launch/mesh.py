"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run entry
point must set XLA_FLAGS before any jax initialization. Simulated
multi-device runs on the CPU take their device count from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the environment.
"""
from __future__ import annotations


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax's default is Explicit axes, under which sharding-in-types rejects
    gathers and adds over mixed-sharded operands (``ShardingTypeError``).
    Every mesh in this repo is built here: the partitioner (and the
    ``with_sharding_constraint`` hints in ``distrib.sharding``) places
    intermediates."""
    import jax
    from jax.sharding import AxisType
    shape = tuple(shape)
    return jax.make_mesh(shape, tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """The fleet meshes: (data=16, model=16) per pod, x2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def parse_shape(spec: str):
    """Parse a ``'DxM'`` mesh-shape string: ``'2x2'`` -> ``(2, 2)``.

    The CLI grammar shared by ``repro.launch.prune --mesh`` and
    ``repro.launch.serve --mesh-shape`` (axis names come from
    ``make_mesh``'s defaults: trailing names of ('pod','data','model'))."""
    return tuple(int(x) for x in spec.lower().split("x"))


def make_mesh(shape, axes=None):
    """Arbitrary mesh for tests/examples (e.g. (2,4) on 8 host devices).

    Args:
      shape: device-grid shape, e.g. ``(2, 4)`` = 2-way data x 4-way model.
      axes: axis names; defaults to the trailing names of
        ('pod', 'data', 'model') matching ``len(shape)``.
    """
    if axes is None:
        axes = ("data", "model")[-len(shape):] if len(shape) <= 2 \
            else ("pod", "data", "model")
    return auto_mesh(shape, axes)
