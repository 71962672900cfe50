"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (required for the dry-run's forced 512 host
devices to be configured before first jax init).

Interpretation (DESIGN.md §4): `model` = 16-chip scale-up domain (TP/EP),
`data` = 16 scale-up domains wired by 16 photonic rails (FSDP/DP; rail k
connects model-rank-k chips of all domains), `pod` = cross-pod DP
(hierarchical rings over rails).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh for the 8-virtual-device test suite."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
