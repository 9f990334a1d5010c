"""Production mesh factory.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device,
while the dry-run sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before its first jax import.

Every axis is ``Auto``: the program places arrays through the logical rules
(``with_sharding_constraint`` and ``shard_map`` in ``repro.sharding`` and
``repro.models.moe``), which may name Auto axes only. ``jax.make_mesh``
defaults to Explicit axes, on which both fail.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_debug_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (TPU v5e); 2 pods for the multi-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for subprocess sharding tests (8 host devices)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
