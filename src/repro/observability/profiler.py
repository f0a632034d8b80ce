"""Puts the tracer's spans on the JAX profiler's trace.

``install()`` gives the process-wide ``TRACER`` a probe
(``TraceAnnotation.is_enabled``: is a ``jax.profiler`` session recording?)
and an annotation factory (``jax.profiler.TraceAnnotation``).  From then on
any profiler session — ``jax.profiler.trace(dir)``, ``start_trace``, or a
capture through the profiler server — also records the program's spans, as
``repro.<span name>`` events on the host plane, on the device trace's
clock.  The jax-importing packages call it on import (``repro.core``), so
``repro.observability.trace`` itself stays stdlib-only.
"""
from __future__ import annotations

from repro.observability.trace import TRACER


def install() -> None:
    """Hook ``TRACER`` to JAX's profiler (idempotent)."""
    from jax.profiler import TraceAnnotation
    TRACER.set_profiler_hook(TraceAnnotation.is_enabled, TraceAnnotation)
