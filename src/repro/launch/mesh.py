"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked on first jax init, and the 512-
device dry-run must set XLA_FLAGS before that happens).

Two mesh families:
  * NN substrate mesh:    (data=16, model=16)  /  (pod=2, data=16, model=16)
  * Federated Forest mesh: the 'model' axis is renamed to the protocol's
    'parties' axis and 'data' to 'trees' (tree-parallel bagging) — same
    chips, the axis names bind the paper's roles (DESIGN.md §4).
"""
from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_forest_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "trees", "parties") if multi_pod else ("trees", "parties")
    return compat.make_mesh(shape, axes)


def make_host_mesh(n: int = 1, axes=("data", "model"),
                   shape=None) -> jax.sharding.Mesh:
    """Small in-process mesh for tests (uses however many devices exist)."""
    n = n or len(jax.devices())
    shape = shape or (1, n)
    return compat.make_mesh(shape, axes)
