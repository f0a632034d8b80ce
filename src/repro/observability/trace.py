"""Hierarchical spans with explicit cross-process context propagation.

Span model
----------
A *span* is a named, timed interval with a trace id, a span id, and an
optional parent span id.  Spans nest through a thread-local stack: the
innermost open span on the current thread is the parent of the next one
opened.  A *trace* is the set of spans sharing one trace id — one
distributed fit yields one trace covering the coordinator's per-level
rounds, each party worker's op execution, retry/backoff sleeps, and
circuit-breaker flips.

Cross-process propagation is explicit: ``current_context()`` returns the
``{"tid", "sid"}`` pair of the innermost open span (or ``None``), the
transport attaches it to outgoing frames under the ``_trace`` key, and a
worker wraps message handling in ``TRACER.attach(ctx)`` so its spans
parent under the coordinator's span even though they live in another OS
process.  Span start times are wall-clock epoch seconds (comparable
across processes); durations come from ``perf_counter`` deltas.

Zero cost when disabled: ``span()`` returns a shared no-op singleton and
``current_context()`` returns ``None``, so no allocation happens, no
span ids are minted, and — critically — no ``_trace`` key is ever added
to wire messages (disabled-path traffic is byte-identical to
uninstrumented code).

Profiler sessions: a hook (``set_profiler_hook``, installed by
:mod:`repro.observability.profiler` when the jax-importing packages load)
also arms recording while a ``jax.profiler`` session records.  Each span
opened then is also opened as a profiler event named ``repro.<name>`` on
the same thread, so it lands on the trace's host plane, nested as the spans
nest, on the device trace's clock.  The disabled path pays one probe call
per span open.

Totals: while recording, the tracer also keeps a count and a sum of
seconds per span name (``totals()``), which the span buffer's bound does
not limit; ``add(name, seconds)`` counts a wait the program measured
between two points it knows.  ``reset()`` clears them.

Privacy: span names/attributes are metadata only.  Attribute values are
restricted to scalars (str/int/float/bool/None) and short tuples of
scalars; anything array-like raises ``TypeError``.  The static twin is
the egress linter: ``span``/``event``/``observe`` and the exporters are
registered wire-sensitive sinks in ``analysis/policy.py``, so a tainted
``.x``/``.ids``/``.y`` value reaching a span is a lint failure.

This module imports only the stdlib (no jax, no repro packages) so the
transport layer can depend on it.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time

__all__ = ["Tracer", "TRACER", "current_context"]

_MAX_SPANS = 65536
_MAX_ATTR_TUPLE = 32
_SCALARS = (str, int, float, bool, type(None))


def _check_attrs(attrs):
    """Validate that every attribute value is plain metadata.

    Raises TypeError on arrays / dicts / arbitrary objects so raw data
    cannot ride along a span even if the linter is bypassed at runtime.
    """
    for k, v in attrs.items():
        if isinstance(v, _SCALARS):
            continue
        if isinstance(v, (tuple, list)) and len(v) <= _MAX_ATTR_TUPLE and all(
                isinstance(e, _SCALARS) for e in v):
            attrs[k] = tuple(v)
            continue
        raise TypeError(
            f"span attribute {k!r} must be a scalar or short tuple of "
            f"scalars, got {type(v).__name__} (metadata-only payloads)")
    return attrs


class _NoopSpan:
    """Shared do-nothing span handle returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()

#: Prefix of the profiler events that carry program spans.
PROFILER_PREFIX = "repro."


class _SpanHandle:
    """An open span; context manager that records itself on exit."""

    __slots__ = ("_tracer", "name", "category", "tid", "sid", "parent",
                 "t0", "_pc0", "attrs", "_thread", "_event")

    def __init__(self, tracer, name, category, tid, sid, parent, attrs):
        self._tracer = tracer
        self.name = name
        self.category = category
        self.tid = tid
        self.sid = sid
        self.parent = parent
        self.attrs = attrs
        self.t0 = time.time()
        self._pc0 = time.perf_counter()
        self._thread = threading.current_thread().name
        self._event = None      # open profiler event, if a session records

    def set(self, **attrs):
        self.attrs.update(_check_attrs(attrs))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._tracer._finish(self)
        return False


class Tracer:
    """Process-local span recorder with a bounded buffer.

    Enabled via the ``REPRO_TRACE=1`` environment variable or
    ``enable()``.  Even when disabled, ``attach(ctx)`` with a non-None
    remote context arms recording on that thread — a worker process that
    never saw the env var still records spans for traced coordinator
    messages — and so does a running profiler session, once a profiler
    hook is installed.
    """

    def __init__(self, enabled: bool | None = None, process: str | None = None):
        if enabled is None:
            enabled = os.environ.get("REPRO_TRACE", "") == "1"
        self._enabled = bool(enabled)
        self.process = process if process is not None else f"pid{os.getpid()}"
        self._ids = itertools.count(1)
        self._buf = collections.deque(maxlen=_MAX_SPANS)
        self._local = threading.local()
        # name -> [count, seconds]; fleet cells drain on a thread pool
        self._totals: dict[str, list] = {}
        self._totals_lock = threading.Lock()
        self._probe = None          # () -> bool: a profiler session records
        self._annotate = None       # name -> profiler event (context manager)

    # ------------------------------------------------------------ state
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    def reset(self):
        """Drop buffered spans, the totals and this thread's context."""
        self._buf.clear()
        with self._totals_lock:
            self._totals.clear()
        self._local.stack = []
        self._local.remote = 0

    def set_profiler_hook(self, probe, annotate) -> None:
        """Record while a profiler session records, and mirror each span
        opened then into it.

        ``probe()`` says whether a session is recording; ``annotate(name)``
        returns a context manager that opens an event on the session's host
        trace.  Both come from the caller so this module stays
        stdlib-only."""
        self._probe, self._annotate = probe, annotate

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _active(self) -> bool:
        return self._enabled or getattr(self._local, "remote", 0) > 0

    def _profiling(self) -> bool:
        probe = self._probe
        return probe is not None and probe()

    def _next_sid(self) -> str:
        return f"{self.process}/{next(self._ids)}"

    # ---------------------------------------------------------- context
    def current_context(self):
        """``{"tid", "sid"}`` of the innermost open span, or ``None``."""
        st = getattr(self._local, "stack", None)
        if not st:
            return None
        tid, sid = st[-1]
        return {"tid": tid, "sid": sid}

    def attach(self, ctx):
        """Context manager parenting this thread's spans under a remote
        context dict (``{"tid", "sid"}``).  ``ctx=None`` is a no-op."""
        return _Attach(self, ctx)

    # ------------------------------------------------------------ spans
    def span(self, name: str, category: str = "host", **attrs):
        """Open a span as a context manager; no-op singleton when off."""
        profiling = self._profiling()
        if not (profiling or self._active()):
            return _NOOP
        return self._begin(name, category, attrs, profiling)

    def begin(self, name: str, category: str = "host", **attrs):
        """Manually open a span (pair with ``finish``, on the same thread);
        None when off.  For spans whose open and close sit in different
        places of one function, e.g. around a pump with several exits.
        """
        profiling = self._profiling()
        if not (profiling or self._active()):
            return None
        return self._begin(name, category, attrs, profiling)

    def finish(self, handle):
        if handle is not None and handle is not _NOOP:
            self._finish(handle)

    def event(self, name: str, category: str = "host", **attrs):
        """Record a zero-duration instant span."""
        profiling = self._profiling()
        if not (profiling or self._active()):
            return
        h = self._begin(name, category, attrs, profiling)
        self._finish(h)

    def add(self, name: str, seconds: float) -> None:
        """Count ``seconds`` under ``name`` in the totals, while recording:
        a wait the program measured between two points it knows (a
        request's time in the queue), where no span could be open."""
        if self._profiling() or self._active():
            self._count(name, seconds)

    def totals(self) -> dict[str, tuple[int, float]]:
        """``{name: (count, seconds)}`` of every span finished and every
        ``add`` while recording since the last ``reset()``."""
        with self._totals_lock:
            return {k: (v[0], v[1]) for k, v in self._totals.items()}

    def _count(self, name, seconds):
        with self._totals_lock:
            entry = self._totals.get(name)
            if entry is None:
                self._totals[name] = [1, seconds]
            else:
                entry[0] += 1
                entry[1] += seconds

    def _begin(self, name, category, attrs, profiling):
        st = self._stack()
        if st:
            tid, parent = st[-1]
        else:
            tid, parent = f"t{self._next_sid()}", None
        sid = self._next_sid()
        h = _SpanHandle(self, name, category, tid, sid, parent,
                        _check_attrs(attrs))
        st.append((tid, sid))
        if profiling:
            h._event = self._annotate(PROFILER_PREFIX + name)
            h._event.__enter__()
        return h

    def _finish(self, h):
        dur = time.perf_counter() - h._pc0
        if h._event is not None:
            h._event.__exit__(None, None, None)
            h._event = None
        self._count(h.name, dur)
        st = self._stack()
        # Pop back to (and including) this span; tolerates overlapping
        # manual begin/finish by searching instead of asserting order.
        for i in range(len(st) - 1, -1, -1):
            if st[i][1] == h.sid:
                del st[i:]
                break
        self._buf.append({
            "name": h.name, "cat": h.category, "tid": h.tid, "sid": h.sid,
            "parent": h.parent, "t0": h.t0, "dur": dur,
            "proc": self.process, "thread": h._thread,
            "attrs": dict(h.attrs),
        })

    # ----------------------------------------------------------- export
    def adopt(self, span_dict: dict):
        """Append a span recorded by another process (telemetry rollup)."""
        if isinstance(span_dict, dict) and "name" in span_dict:
            self._buf.append(dict(span_dict))

    def spans(self) -> list[dict]:
        """Snapshot of buffered spans (oldest first), without clearing."""
        return list(self._buf)

    def drain(self) -> list[dict]:
        """Pop and return all buffered spans (oldest first)."""
        out = []
        while True:
            try:
                out.append(self._buf.popleft())
            except IndexError:
                return out


class _Attach:
    __slots__ = ("_tracer", "_ctx", "_pushed")

    def __init__(self, tracer, ctx):
        self._tracer = tracer
        self._ctx = ctx
        self._pushed = False

    def __enter__(self):
        ctx = self._ctx
        if ctx and "tid" in ctx and "sid" in ctx:
            self._tracer._stack().append((str(ctx["tid"]), str(ctx["sid"])))
            self._tracer._local.remote = getattr(
                self._tracer._local, "remote", 0) + 1
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            st = self._tracer._stack()
            if st:
                st.pop()
            self._tracer._local.remote = max(
                0, getattr(self._tracer._local, "remote", 1) - 1)
        return False


#: Process-wide tracer.  Workers re-tag ``TRACER.process`` on startup.
TRACER = Tracer()


def current_context():
    """Module-level convenience for the transport layer."""
    return TRACER.current_context()
