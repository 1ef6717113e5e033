"""Production meshes (TPU v5e). Functions, not module constants — importing
this module never touches jax device state."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices: Optional[Sequence] = None):
    """The one mesh constructor: every axis ``Auto``, so GSPMD propagates
    shardings through the vmapped client step's reshapes (``jax.make_mesh``
    defaults to ``Explicit`` axes, which reject them)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def activate_mesh(mesh):
    """Make ``mesh`` the ambient mesh (``jax.set_mesh``) so sharding-aware
    module paths (``get_abstract_mesh`` readers in models/layers,
    models/moe, models/transformer) see its axis names during trace."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per-chip usable)
