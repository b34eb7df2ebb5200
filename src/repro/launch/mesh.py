"""Mesh construction for single-pod / multi-pod execution.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and nothing here may run earlier.

Every mesh here has Auto axes.  ``jax.make_mesh`` defaults to Explicit
axes, under which slicing a sharded result on the host (the engine cuts
each family's rows out of a bucket) raises ``ShardingTypeError``.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh: 16x16 per pod, 2 pods multi-pod.

    When more devices exist than the mesh needs (the dry-run forces 512
    host devices; single-pod uses 256), the first prod(shape) devices are
    used — matching how a per-pod launch sees only its pod's chips.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} "
            "(the dry-run must set XLA_FLAGS before any jax import)")
    return _auto_mesh(shape, axes, devices[:n])


def make_mesh_for(n_devices: int | None = None, model_parallel: int = 1,
                  pods: int = 1) -> Mesh:
    """Elastic variant: build a (pod, data, model) mesh from whatever devices
    are available (used by tests and the elastic-resume path)."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n % (model_parallel * pods):
        raise ValueError(f"{n} devices not divisible by "
                         f"model={model_parallel} x pods={pods}")
    data = n // (model_parallel * pods)
    if pods > 1:
        return _auto_mesh((pods, data, model_parallel),
                          ("pod", "data", "model"))
    return _auto_mesh((data, model_parallel), ("data", "model"))


def mesh_info(mesh: Mesh) -> dict:
    return {
        "axis_names": tuple(mesh.axis_names),
        "shape": dict(mesh.shape),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
    }
