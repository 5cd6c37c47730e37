"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run process
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
import; everything else sees the 1 real CPU device.

Mesh layout:
  single-pod : (16, 16)     axes ("data", "model")          = 256 chips
  multi-pod  : (2, 16, 16)  axes ("pod", "data", "model")   = 512 chips

``pod`` is pure data parallelism over the slow cross-pod links by default
(the collective cost model quantifies why; see EXPERIMENTS.md §Roofline).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Mesh over the first prod(shape) local devices (supports building the
    256-chip mesh inside the 512-device dry-run process)."""
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, have {len(devs)} "
            f"(dry-run requires XLA_FLAGS=--xla_force_host_platform_device_count)")
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes), devices=devs[:n])


def smoke_mesh(model: int = 2, data: Optional[int] = None):
    """Largest (data, model) mesh the *local* device set supports (tests)."""
    n = len(jax.devices())
    model = min(model, n)
    data = data or max(1, n // model)
    return make_mesh((data, model), ("data", "model"))


def devices_per_pod(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.axis_shape
                     if hasattr(mesh, "axis_shape") else mesh.devices.shape))
    pods = sizes.get("pod", 1)
    total = 1
    for s in (mesh.devices.shape if hasattr(mesh, "devices") else []):
        total *= s
    return total // pods if pods else total
