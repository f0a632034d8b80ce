"""Runners that bind the SPMD federated protocol to an execution substrate.

The builder/predictor in tree.py / prediction.py are written once against the
``parties`` axis name.  They execute under:

  * ``run_simulated``: vmap with axis_name — M parties on one host.  This is
    the CPU test/benchmark path and is semantically identical to the
    distributed run (collectives have the same meaning under vmap).
  * ``run_sharded``: shard_map over a mesh axis literally named "parties" —
    the production / dry-run path (mesh from launch/mesh.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import PARTY_AXIS


def run_simulated(fn: Callable[..., Any], party_args: tuple, shared_args: tuple = ()):
    """vmap over the leading party axis of ``party_args``; broadcast the rest."""
    in_axes = (0,) * len(party_args) + (None,) * len(shared_args)
    return jax.vmap(fn, in_axes=in_axes, axis_name=PARTY_AXIS)(
        *party_args, *shared_args)


def jit_simulated(fn: Callable[..., Any], n_party: int, n_shared: int,
                  **jit_kw):
    """jit(run_simulated(fn)) with the party/shared split baked in."""
    @functools.partial(jax.jit, **jit_kw)
    def wrapped(*args):
        return run_simulated(fn, args[:n_party], args[n_party:n_party + n_shared])
    return wrapped


def sharded_program(fn: Callable[..., Any], mesh: Mesh, n_party: int,
                    n_shared: int, shared_specs=None, out_specs=None):
    """shard_map ``fn`` over the mesh axis named ``PARTY_AXIS``.

    Party args shard their leading M axis one-party-per-shard (the axis size
    must equal M); inside the mapped body the local size-1 party dim is
    squeezed so ``fn`` sees exactly what it sees under ``run_simulated``, and
    re-expanded on the way out.  ``shared_specs`` places the shared args
    (default: replicated); ``out_specs`` defaults to party-stacked outputs.
    Returns the un-jitted program — callers jit/lower it (the AOT serving
    path) or wrap it in ``run_sharded`` for eager use.
    """
    shared_specs = tuple(shared_specs) if shared_specs is not None else \
        (P(),) * n_shared
    if len(shared_specs) != n_shared:
        raise ValueError(f"{n_shared} shared args, {len(shared_specs)} specs")
    in_specs = (P(PARTY_AXIS),) * n_party + shared_specs
    out_specs = P(PARTY_AXIS) if out_specs is None else out_specs

    def local(*args):
        party = [jax.tree.map(lambda a: a[0], a) for a in args[:n_party]]
        out = fn(*party, *args[n_party:])
        return jax.tree.map(lambda a: a[None], out)

    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def run_sharded(fn: Callable[..., Any], party_args: tuple,
                shared_args: tuple = (), *, mesh: Mesh,
                shared_specs=None, out_specs=None):
    """Run ``fn`` SPMD over the mesh's "parties" axis (see sharded_program)."""
    prog = sharded_program(fn, mesh, len(party_args), len(shared_args),
                           shared_specs=shared_specs, out_specs=out_specs)
    return prog(*party_args, *shared_args)


def replicate_to_mesh(x, mesh: Mesh):
    """Device-put a host array replicated over every mesh axis."""
    return jax.device_put(x, NamedSharding(mesh, P()))
