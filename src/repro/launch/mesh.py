"""Mesh construction for single-pod and multi-pod runs.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, everything else sees the real device count.
"""

from __future__ import annotations

import jax
import numpy as np

# Canonical mesh axis names.
POD_AXIS = "pod"
DATA_AXIS = "data"    # doubles as the FSDP axis
MODEL_AXIS = "model"  # tensor-parallel axis


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh: 16x16 single pod, or 2x16x16 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """A mesh of Auto axes over the first prod(shape) of ``devices``
    (default: all local devices)."""
    devices = list(jax.devices() if devices is None else devices)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh {shape} needs {n} devices, "
                         f"have {len(devices)}")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(model_parallel: int = 1):
    """A mesh over whatever devices exist locally (smoke tests, examples)."""
    n = len(jax.devices())
    dp = max(1, n // model_parallel)
    return make_mesh((dp, model_parallel), (DATA_AXIS, MODEL_AXIS))


def mesh_axis_names(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes over which the batch is sharded (pod+data when multi-pod)."""
    names = mesh_axis_names(mesh)
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in names)


def num_chips(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
