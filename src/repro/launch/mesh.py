"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import, and everything else sees the real single device.

Every mesh axis is ``Auto``: the models place activations with
``with_sharding_constraint`` (``repro.distributed.api.shard``) and leave
the rest to the partitioner, which ``jax.make_mesh``'s default of
``Explicit`` axes refuses.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1, pod: int = 1):
    """Small meshes for CPU-device tests (requires enough host devices)."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
