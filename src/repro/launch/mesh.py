"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls ``make_production_mesh``.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: ``ShardingRules`` gives the
    shardings of a step's inputs and outputs, and the compiler propagates
    them through the model (an ``Explicit`` mesh would instead demand a
    sharding for every intermediate the model computes)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 0):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _auto_mesh((data, model), ("data", "model"))


# Hardware constants for the roofline model (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s/link
