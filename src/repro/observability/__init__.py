"""Federated observability: cross-process tracing + telemetry registry.

Three pieces, all stdlib-only on the hot path:

- :mod:`repro.observability.trace` — hierarchical spans whose context
  (``{"tid", "sid"}``) rides the transport frames, so one distributed
  fit is one connected trace across the coordinator and every party
  process.  No-op (and wire-byte-identical) when disabled; enable with
  ``REPRO_TRACE=1`` or ``TRACER.enable()``.
- :mod:`repro.observability.registry` — counters / gauges / bounded
  histograms with pooled quantiles; party snapshots roll up to the
  coordinator through the worker ``telemetry`` op.
- :mod:`repro.observability.export` — JSONL + Chrome-trace export and
  the critical-path report behind the ``repro-trace`` CLI.

A ``jax.profiler`` session also records the tracer's spans, as
``repro.<name>`` events on the profiler's host trace
(:mod:`repro.observability.profiler`, installed when ``repro.core`` loads).
"""
from repro.observability.registry import (Counter, Gauge, Histogram,
                                          Registry, REGISTRY)
from repro.observability.trace import TRACER, Tracer, current_context
from repro.observability.export import (chrome_trace, critical_path,
                                        export_jsonl, format_report,
                                        read_jsonl, write_chrome_trace)

__all__ = [
    "TRACER", "Tracer", "current_context",
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram",
    "export_jsonl", "read_jsonl", "chrome_trace", "write_chrome_trace",
    "critical_path", "format_report",
]
