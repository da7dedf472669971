"""Production meshes.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") -- "pod"
crosses the inter-pod DCN/ICI boundary; batch shards over it, parameters
replicate over it (pure DP between pods; optionally int8-compressed
gradient sync, see optim/compression.py).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run pins XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    """Auto axes: ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (``sharding.constrain``) refuses."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)))
    assert len(devs) >= n, (
        f"need {n} devices for mesh {shape}; have {len(devs)} "
        "(dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
        "=512 before any jax import)")
    from jax.experimental import mesh_utils
    dm = mesh_utils.create_device_mesh(shape, devices=devs[:n])
    return jax.sharding.Mesh(dm, axes, axis_types=_auto(len(shape)))


def make_debug_mesh(model: int = 1, data: int = 1):
    """Small mesh over however many (CPU) devices tests spawned."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
