"""Per-layer metrics read from the program's own spans.

The program's tracer (``repro.observability.TRACER``) records while a
profiler session records, so in a traced run its totals hold the count and
the seconds of each span name over the measured window alone (set-up and
the checks run outside the session).  A reader divides a span's seconds by
one of the cell's counters (jobs, waves, requests).  Where the program keeps
no span totals, or the span never opened in the window, it returns None.
"""
from __future__ import annotations


def seconds(name: str) -> float | None:
    """Seconds under span ``name`` in the window, or None."""
    from repro.observability import TRACER
    totals = getattr(TRACER, "totals", None)
    if totals is None:
        return None
    count, secs = totals().get(name, (0, 0.0))
    return secs if count else None


def _per(secs: float | None, n) -> float | None:
    return None if secs is None or not n else secs / n


def per_job_s(ctx: dict, name: str) -> float | None:
    """Seconds per fit job (``counters["jobs"]``)."""
    return _per(seconds(name), ctx["counters"].get("jobs"))


def per_wave_ms(ctx: dict, name: str) -> float | None:
    """Milliseconds per served wave (``counters["serve"]["waves"]``)."""
    serve = ctx["counters"].get("serve") or {}
    v = _per(seconds(name), serve.get("waves"))
    return None if v is None else 1e3 * v


def per_request_ms(ctx: dict, name: str) -> float | None:
    """Milliseconds per request (``counters["serve"]["requests"]``)."""
    serve = ctx["counters"].get("serve") or {}
    v = _per(seconds(name), serve.get("requests"))
    return None if v is None else 1e3 * v
