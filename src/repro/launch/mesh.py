"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — smoke tests keep their single CPU
device; only the dry-run forces 512 host devices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes, devices) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have "
            f"{len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(see repro.launch.dryrun)")
    return _make_mesh(shape, axes, devices)


def make_test_mesh(shape: Sequence[int] = (2, 4),
                   axes: Sequence[str] = ("data", "model")) -> Mesh:
    """Small mesh over however many host devices exist (tests)."""
    n = 1
    for s in shape:
        n *= s
    return _make_mesh(tuple(shape), tuple(axes), jax.devices()[:n])


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size
