"""Mesh construction (TPU v5e numbers).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets --xla_force_host_platform_device_count first.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

# hardware constants used by the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """The repo's one mesh constructor. Every axis is ``Auto``: the models
    place activations with ``with_sharding_constraint`` on named axes and let
    GSPMD propagate the rest, which ``Explicit`` axes (``jax.make_mesh``'s
    default since JAX 0.7) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
