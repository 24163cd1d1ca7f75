"""Production meshes for the multi-pod dry-run (TPU v5e target).

Defined as functions so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis pinned to ``AxisType.Auto``, the
    semantics all the pjit code here assumes (``jax.make_mesh`` defaults
    to ``Explicit``)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


# v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
