"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; ``dryrun.py`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to materialize the placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  JAX makes ``Explicit`` axes by
    default, and the sharding rules here steer GSPMD through
    ``with_sharding_constraint``, which only ``Auto`` axes accept."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ('data', 'model'), 256 chips (TPU v5e pod).
    Multi-pod: (2, 16, 16) = ('pod', 'data', 'model'), 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


HW = {
    # TPU v5e per-chip constants for the roofline analysis
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_bw_per_link": 50e9,
    "hbm_bytes": 16e9,
}
