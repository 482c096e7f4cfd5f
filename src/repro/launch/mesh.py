"""Production mesh definitions (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax

# v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_BF16_FLOPS = 197e12      # 197 TFLOP/s
HBM_BW = 819e9                # 819 GB/s
ICI_BW = 50e9                 # ~50 GB/s per link


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the same axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
