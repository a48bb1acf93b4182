"""Production mesh construction.

The target is TPU v5e: one pod = 16 x 16 = 256 chips with axes
("data", "model"); the multi-pod configuration stacks 2 pods = 512 chips with
axes ("pod", "data", "model").  Everything is a function -- importing this
module never touches jax device state.  Activate a mesh with
``jax.set_mesh(mesh)``: the model's sharding constraints read it from there.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_smoke_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny ("data", "model") mesh over the process's first devices."""
    return _make((n_data, n_model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link (~3 links usable per axis-neighbour topology)
