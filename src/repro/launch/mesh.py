"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its first
jax call, and anything that eagerly built a mesh at import time would
lock the device count too early.

Target hardware: TPU v5e pods — 256 chips/pod arranged (16, 16) with
axes ("data", "model"); the multi-pod mesh prepends a "pod" axis for the
2-pod, 512-chip configuration.  Scaling to 1000+ nodes = more pod-axis
entries; all sharding rules are written against logical names and never
against mesh extents.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the sharding rules constrain
    activations with ``with_sharding_constraint``, which refuses the
    Explicit axes ``make_mesh`` builds by default."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_devices: Optional[int] = None):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    if n >= 4:
        return auto_mesh((2, n // 2), ("data", "model"))
    return auto_mesh((1, n), ("data", "model"))


def mesh_info(mesh) -> Tuple[int, dict]:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for v in sizes.values():
        n *= v
    return n, sizes
