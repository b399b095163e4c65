"""Mesh construction for the production pods.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py is allowed to force 512 host devices).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.sharding import (
    MULTI_POD_RULES,
    SINGLE_POD_RULES,
    MeshEnv,
    zero1_rules,
)

# v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_env(mesh, overrides: dict | None = None) -> MeshEnv:
    """MeshEnv with the right rules for this mesh (+ hillclimb overrides)."""
    rules = MULTI_POD_RULES if "pod" in mesh.shape else SINGLE_POD_RULES
    rules = zero1_rules(rules)
    if overrides:
        rules = dict(rules, **overrides)
    return MeshEnv(mesh=mesh, rules=rules)
