"""Production mesh builders.

Defined as functions (not module constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _auto_axes(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod outer axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic runtime resizing)."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_auto_axes(len(axes)))


# TPU v5e-like hardware model (per chip) — values from the assignment.
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16e9,           # capacity
}
