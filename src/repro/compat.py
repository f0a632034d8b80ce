"""Mesh construction with the axis semantics the protocol is written for.

``jax.make_mesh`` (jax >= 0.7) makes every axis ``Explicit``: shardings then
become part of each array's type, and the federated programs' slicing of
party-stacked arrays is refused ("slicing on sharded dims ... not divisible
by mesh axes").  The protocol bodies are written for ``Auto`` axes — GSPMD
propagates shardings and ``shard_map`` bodies see plain local arrays — so
every mesh in the repo is built here.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
