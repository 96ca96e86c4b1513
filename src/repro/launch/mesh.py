"""Device meshes.  ``make_mesh`` is the one constructor every mesh in the
program and its tests goes through: it owns the axis-type decision.  Every
axis is ``AxisType.Auto`` (GSPMD propagates shardings from the annotations
the engine places), which the serving and training code is written for;
``jax.make_mesh``'s own default builds ``Explicit`` axes, under which the
embedding gather and ``with_sharding_constraint`` on model-sharded tables
fail to type-check.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.

Every constructor validates the requested shape against
``jax.device_count()`` up front (``validate_mesh_shape``) — a bad shape
used to surface as an inscrutable partitioning error deep inside the
first jit; now it raises a one-line ValueError before any program is
traced.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def validate_mesh_shape(shape: Sequence[int], axes: Sequence[str],
                        *, device_count: Optional[int] = None
                        ) -> Tuple[int, ...]:
    """Check a requested mesh topology before any jit sees it.

    Raises ``ValueError`` with an actionable message when the axis lists
    mismatch, an axis size is not a positive integer, or the shape needs
    more devices than the backend exposes (the common failure: forgetting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU).
    Returns the shape as a tuple on success.
    """
    shape = tuple(shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(
            f"mesh shape {shape} has {len(shape)} axes but names {axes} "
            f"have {len(axes)}")
    for name, size in zip(axes, shape):
        if not isinstance(size, int) or isinstance(size, bool) or size < 1:
            raise ValueError(
                f"mesh axis {name!r} must be a positive int, got {size!r}")
    if len(set(axes)) != len(axes):
        raise ValueError(f"duplicate mesh axis names in {axes}")
    need = math.prod(shape)
    have = jax.device_count() if device_count is None else device_count
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are visible — shrink the mesh, or (CPU smoke runs) "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before the first jax import")
    return shape


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """Mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` visible devices), every axis ``Auto``."""
    devices = list(jax.devices() if devices is None else devices)
    shape = validate_mesh_shape(shape, axes, device_count=len(devices))
    return jax.make_mesh(shape, tuple(axes),
                         devices=devices[:math.prod(shape)],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis (512)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_serving_mesh(data: int = 1, model: int = 1):
    """Serving-engine mesh: slot-axis DP x head/context TP.

    Uses the first ``data * model`` visible devices (a serving host may
    dedicate the remainder to a second engine behind the router).
    """
    return make_mesh((data, model), ("data", "model"))
