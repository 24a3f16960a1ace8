"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.

Topology: TPU v5e pods of 256 chips. Single-pod mesh is 16×16
("data", "model"); the multi-pod mesh adds a leading "pod" axis
(2×16×16 = 512 chips) that composes with "data" for batch sharding —
the lowest-bandwidth (DCN) axis carries only the per-step gradient
all-reduce, optionally compressed (optim.compress).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(shape), devices=devices
    )
