"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked on first jax init, and only
``dryrun.py`` may set the 512-device XLA flag before that happens).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh whose axes are all Auto: ``jax.make_mesh`` makes Explicit
    axes by default, and ``with_sharding_constraint`` and the
    ``in_shardings`` rules here name Auto axes only."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading 2-pod
    axis (2x16x16 = 512 chips). ``pod`` composes with ``data`` as the outer
    data-parallel/FSDP dimension (DESIGN.md 5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh() -> jax.sharding.Mesh:
    """1x1 mesh over the single real device (smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def mesh_num_devices(mesh: jax.sharding.Mesh) -> int:
    return int(mesh.devices.size)
