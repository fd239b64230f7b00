"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls these.
"""

from __future__ import annotations

import jax

__all__ = ["make_auto_mesh", "make_production_mesh", "make_local_mesh"]


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the models place
    activations with sharding constraints and let the partitioner pick
    gather/reshape layouts, which ``jax.make_mesh``'s default
    ``Explicit`` axes refuse."""
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh over the real local device(s) — smoke tests / examples."""
    n = len(jax.devices())
    return make_auto_mesh((n, 1), ("data", "model"))
