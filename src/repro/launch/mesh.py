"""Production mesh factories (assignment contract).

Functions, not module-level constants, so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single-pod, (2, 16, 16) pod x data x model
    multi-pod — 256 / 512 TPU v5e chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh over forced host devices — used by CPU integration
    tests (8 devices) to exercise the exact same sharding rules."""
    return make_mesh((data, model), ("data", "model"))
