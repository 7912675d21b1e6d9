"""Production mesh definitions (TPU v5e).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the decentralized-gossip axis for the >256 GB architectures
(DESIGN.md Sec. 3) and the cross-DCN axis the paper's communication
efficiency targets.

Functions, not module-level constants: importing this module never touches
jax device state.

Every mesh the repo builds goes through :func:`make_mesh`, which gives
all axes the ``Auto`` type.  The code is GSPMD-style throughout (``jit``
shardings plus ``with_sharding_constraint``), which only accepts Auto
axes; newer ``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto (module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link (~)
