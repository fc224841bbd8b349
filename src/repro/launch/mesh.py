"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

  single-pod:  (data=16, model=16)            = 256 chips (one v5e pod)
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips

``pod`` composes with ``data`` for DP/FSDP (512-way parameter and optimizer
sharding for the 1T arch) — see sharding_rules.py.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over real local devices (tests/examples on CPU)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """Auto axis types: sharding constraints and shard_map may name any
    axis (``jax.make_mesh`` defaults to Explicit axes)."""
    return (jax.sharding.AxisType.Auto,) * n


# v5e hardware constants for the roofline terms (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
