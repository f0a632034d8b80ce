"""Execution substrates: where the federated protocol runs.

The protocol bodies (core/tree.py, core/prediction.py, core/fedlinear.py)
are written once against the ``parties`` axis name; a Substrate decides how
that axis is realized:

  * ``SimulatedSubstrate``   — vmap on one host (core/protocol.run_simulated).
    The CPU test/benchmark path; collectives have identical semantics.
  * ``ShardedSubstrate``     — shard_map over a mesh whose "parties" axis is
    the protocol axis (core/protocol.run_sharded).  One party per shard,
    optional "trees" axis for bagging tree-parallelism.
  * ``DistributedSubstrate`` — one OS process per party, message-passing
    collectives over localhost sockets, production fault tolerance
    (federation/distributed.py).

Substrates register themselves by name (``register_substrate``, mirroring
the histogram-backend registry of kernels/ops.py), so a new implementation
plugs into every lifecycle surface — Federation.fit/predict/serve,
ForestServer, the launch CLIs — through ``resolve_substrate`` without
touching it.  The protocol also carries the lifecycle seams a real
transport needs — ``compile``/``aot_compile`` (how a program becomes an
executable: jax.jit for in-process substrates, identity/bind for the
message-passing one), ``exchange`` (out-of-band party requests), and
``shutdown`` — with no-op defaults in :class:`InProcessSubstrate`.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Protocol, runtime_checkable

import jax
from jax.sharding import Mesh

from repro.core import protocol
from repro.core.types import PARTY_AXIS


@runtime_checkable
class Substrate(Protocol):
    """Where SPMD party programs execute (duck-typed; see the three impls)."""

    name: str
    mesh: Mesh | None

    def program(self, fn: Callable, n_party: int, n_shared: int, *,
                shared_specs=None, out_specs=None, distributed=None,
                parties=None) -> Callable: ...

    def jit(self, fn: Callable, n_party: int, n_shared: int, **kw) -> Callable: ...

    def compile(self, program: Callable) -> Callable: ...

    def aot_compile(self, program: Callable, *args) -> Callable: ...

    def context(self): ...

    def exchange(self, op: str, payload=None, *, party=None, timeout=None): ...

    def shutdown(self) -> None: ...


class InProcessSubstrate:
    """Shared seams for substrates whose parties live in this process:
    compilation is jax.jit/AOT, there is no transport to exchange over,
    and shutdown has nothing to tear down."""

    def jit(self, fn: Callable, n_party: int, n_shared: int, **kw) -> Callable:
        return jax.jit(self.program(fn, n_party, n_shared, **kw))

    def compile(self, program: Callable) -> Callable:
        """Program -> executable (JIT-wrapped; traces on first call)."""
        return jax.jit(program)

    def aot_compile(self, program: Callable, *args) -> Callable:
        """Program -> ahead-of-time compiled executable for these operands
        (the serving engine's per-bucket warm path)."""
        return jax.jit(program).lower(*args).compile()

    def context(self):
        return contextlib.nullcontext()

    def exchange(self, op: str, payload=None, *, party=None, timeout=None):
        """Out-of-band party requests only exist over a transport."""
        return None

    def shutdown(self) -> None:
        pass


class SimulatedSubstrate(InProcessSubstrate):
    """M parties on one host under vmap — semantically the distributed run."""

    name = "simulated"
    mesh = None
    tree_axis = None

    def program(self, fn: Callable, n_party: int, n_shared: int, *,
                shared_specs=None, out_specs=None, distributed=None,
                parties=None) -> Callable:
        """Callable over (party_args..., shared_args...); sharding specs and
        the distributed protocol spec are accepted (and ignored) so callers
        can stay substrate-agnostic."""
        def run(*args):
            return protocol.run_simulated(
                fn, args[:n_party], args[n_party:n_party + n_shared])
        return run


class ShardedSubstrate(InProcessSubstrate):
    """shard_map over a mesh axis literally named "parties" (one party per
    shard).  A "trees" axis, if present, carries bagging tree-parallelism —
    forest programs shard their per-tree args/outputs over it."""

    name = "sharded"

    def __init__(self, mesh: Mesh):
        if PARTY_AXIS not in mesh.axis_names:
            raise ValueError(
                f"sharded substrate needs a '{PARTY_AXIS}' mesh axis, got "
                f"{mesh.axis_names}")
        self.mesh = mesh

    @property
    def n_parties(self) -> int:
        return int(self.mesh.shape[PARTY_AXIS])

    @property
    def tree_axis(self) -> str | None:
        return "trees" if "trees" in self.mesh.axis_names else None

    def program(self, fn: Callable, n_party: int, n_shared: int, *,
                shared_specs=None, out_specs=None, distributed=None,
                parties=None) -> Callable:
        return protocol.sharded_program(fn, self.mesh, n_party, n_shared,
                                        shared_specs=shared_specs,
                                        out_specs=out_specs)

    def context(self):
        """Mesh context for lowering (resolves in-program sharding names)."""
        return jax.set_mesh(self.mesh)


# ------------------------------------------------------------------- registry
SUBSTRATES: dict[str, Callable[..., Substrate]] = {}


def register_substrate(name: str, factory: Callable[..., Substrate] | None = None):
    """Register a substrate factory under ``name`` (the string accepted by
    ``resolve_substrate`` and every session/server entrypoint).  Factories
    receive ``mesh=``/``parties=`` plus any substrate-specific options.
    Usable as a decorator (``@register_substrate("x")``) or a call
    (``register_substrate("x", factory)``), like kernels/ops.py's backend
    registry."""
    def register(f):
        SUBSTRATES[name] = f
        return f
    return register(factory) if factory is not None else register


@register_substrate("simulated")
def _make_simulated(mesh=None, parties=None, **opts) -> Substrate:
    if opts:
        raise TypeError(f"substrate 'simulated' takes no options, got "
                        f"{sorted(opts)}")
    return SimulatedSubstrate()


@register_substrate("sharded")
def _make_sharded(mesh=None, parties=None, **opts) -> Substrate:
    if mesh is None:
        raise ValueError("substrate='sharded' requires a mesh")
    if opts:
        raise TypeError(f"substrate 'sharded' takes no options, got "
                        f"{sorted(opts)}")
    return ShardedSubstrate(mesh)


@register_substrate("distributed")
def _make_distributed(mesh=None, parties=None, **opts) -> Substrate:
    from repro.federation.distributed import DistributedSubstrate
    if parties is None:
        raise ValueError("substrate='distributed' needs the party count "
                         "(resolve_substrate(..., parties=M))")
    return DistributedSubstrate(parties, **opts)


def default_substrate(sub: Substrate | None = None) -> Substrate:
    """The substrate an estimator runs on when none was injected: vmap
    simulation.  Single owner of the estimators' fallback wiring."""
    return sub if sub is not None else SimulatedSubstrate()


def resolve_substrate(spec: str | Substrate | Any, mesh: Mesh | None = None,
                      parties: int | None = None, **opts) -> Substrate:
    """One-time substrate resolution for a session or server.

    ``spec`` is a registered substrate name (see ``SUBSTRATES``) or an
    already-built Substrate (passed through).  ``parties``, when given, is
    validated against the substrate's own party count (a sharded mesh's
    party-axis size, a distributed coordinator's worker count).  Extra
    keyword options flow to the named factory (e.g. the distributed
    substrate's timeout/retry knobs)."""
    if isinstance(spec, str):
        factory = SUBSTRATES.get(spec)
        if factory is None:
            raise ValueError(f"unknown substrate {spec!r} "
                             f"(registered: {sorted(SUBSTRATES)})")
        sub = factory(mesh=mesh, parties=parties, **opts)
    elif isinstance(spec, Substrate):   # any conforming implementation
        sub = spec
    else:
        raise ValueError(f"unknown substrate {spec!r} "
                         f"(registered: {sorted(SUBSTRATES)}, or pass a "
                         f"Substrate)")
    have = getattr(sub, "n_parties", None)
    if parties is not None and have is not None and int(have) != parties:
        raise ValueError(
            f"substrate {sub.name!r} executes {have} parties but the "
            f"session declares {parties}")
    return sub
