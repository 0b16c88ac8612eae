"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax call).

  single-pod:  (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: ``constrain`` places activations with
    # with_sharding_constraint, which refuses the Explicit axes that
    # jax.make_mesh gives by default
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e-like hardware model used by the roofline analysis (EXPERIMENTS.md)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
