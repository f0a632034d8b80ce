"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone.  What it relies on:

* device planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds
  one event per executed HLO op (fusions, custom calls such as the Pallas
  histogram kernel, collectives; a ``while`` op's event holds its body's),
  ``Async XLA Ops`` the asynchronous ones (copies, collectives started and
  finished apart) and ``XLA Modules`` one event per executed program;
* the harness's own host spans are ``jax.profiler.TraceAnnotation`` events
  named ``bench.<what>`` on the host plane ``/host:CPU``, on the same clock;
  ``bench.window`` spans exactly the measured window.

Per device it takes the union of ``XLA Ops`` intervals inside the window
(busy time), the self time and count per op and the time and count per
program, the time in collectives (synchronous or asynchronous), and the gaps
between busy intervals, each put down to the innermost ``bench.*`` span that
covers its middle ("other" where none does).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all", re.IGNORECASE)
TOP = 10


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _short(name: str) -> str:
    """An op's name: a TPU trace names ops by their whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``); keep what precedes ``=``."""
    return name.split(" = ", 1)[0]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _host_spans(profile) -> list[tuple[float, float, str]]:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


class Labeller:
    """The innermost harness span covering a point of time, by bisection.

    The harness's spans come from one thread and nest, so one sweep over
    them in order of start, with a stack of the open ones, cuts the time
    into segments that each lie under one innermost span (or none)."""

    def __init__(self, spans):
        self.starts: list[float] = []
        self.names: list[str] = []
        stack: list[tuple[float, str]] = []         # (end, name), open spans

        def close_until(t: float) -> None:
            while stack and stack[-1][0] <= t:
                end, _ = stack.pop()
                self._mark(end, stack[-1][1] if stack else "other")

        inner = [s for s in spans if s[2] != WINDOW]
        for lo, hi, name in sorted(inner, key=lambda s: (s[0], -s[1])):
            close_until(lo)
            stack.append((hi, name[len(SPAN_PREFIX):]))
            self._mark(lo, stack[-1][1])
        close_until(float("inf"))

    def _mark(self, t: float, name: str) -> None:
        if self.starts and self.starts[-1] == t:
            self.names[-1] = name
        else:
            self.starts.append(t)
            self.names.append(name)

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        return self.names[i] if i >= 0 else "other"


def _events(line) -> list[tuple[int, int, str]]:
    """(start ns, duration ns, short name) of a line's events, each field
    read once: a serving window holds ~10^6 events with long names."""
    return [(ev.start_ns, ev.duration_ns, _short(ev.name))
            for ev in line.events]


def _self_times(events, w0, w1):
    """(name, self ns, interval) per op inside the window: an op's time
    less that of the ops nested in it (a ``while`` holds its body)."""
    out = []
    stack: list[list] = []          # [end, index into out]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        lo = max(start, w0)
        hi = min(start + dur, w1)
        if hi <= lo:
            continue
        while stack and stack[-1][0] <= lo:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= hi - lo
        out.append([name, hi - lo, (lo, hi)])
        stack.append([hi, len(out) - 1])
    return out


def reduce_profile(profile) -> dict:
    """Numbers of one traced run; times in seconds.

    ``busy_s`` and ``idle_by_host`` are averages over the device planes,
    ``ops``/``modules``/``collective_s`` sums over them; an op's seconds are
    its self time."""
    spans = _host_spans(profile)
    windows = [(lo, hi) for lo, hi, name in spans if name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    busy, collective = [], 0.0
    idle: dict[str, float] = {}
    label_at = Labeller(spans)
    devices = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    is_collective: dict[str, bool] = {}
    for plane in devices:
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (MODULES_LINE, OPS_LINE, ASYNC_LINE)}
        for start, dur, name in lines.get(MODULES_LINE, []):
            lo, hi = max(start, w0), min(start + dur, w1)
            if hi > lo:
                entry = modules.setdefault(name, [0.0, 0])
                entry[0] += (hi - lo) * 1e-9
                entry[1] += 1
        intervals = []
        for name, self_ns, iv in _self_times(lines.get(OPS_LINE, []), w0, w1):
            entry = ops.setdefault(name, [0.0, 0])
            entry[0] += self_ns * 1e-9
            entry[1] += 1
            intervals.append(iv)
        for line in (OPS_LINE, ASYNC_LINE):
            for start, dur, name in lines.get(line, []):
                if name not in is_collective:
                    is_collective[name] = bool(COLLECTIVE.search(name))
                if is_collective[name]:
                    lo, hi = max(start, w0), min(start + dur, w1)
                    collective += max(hi - lo, 0) * 1e-9
        merged = _union(intervals)
        busy.append(sum(hi - lo for lo, hi in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                label = label_at((lo + hi) / 2)
                idle[label] = (idle.get(label, 0.0)
                               + (hi - lo) * 1e-9 / len(devices))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "busy_s": sum(busy) / len(devices) if devices else 0.0,
        "ops": {k: {"s": v[0], "n": v[1]} for k, v in ops.items()},
        "modules": {k: {"s": v[0], "n": v[1]} for k, v in modules.items()},
        "collective_s": collective,
        "idle_by_host": idle,
    }


def op_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Device self seconds and count of the ops whose name matches
    ``pattern`` (a regular expression), summed over devices.  Only the name:
    an op's HLO text also names the ops it reads."""
    rx = re.compile(pattern)
    secs, n = 0.0, 0
    for name, op in reduced["ops"].items():
        if rx.search(name):
            secs += op["s"]
            n += op["n"]
    return secs, n


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: the ten ops that took most device time
    and the host activity under the most idle device time."""
    ops = sorted(((v["s"], k) for k, v in reduced["ops"].items()),
                 reverse=True)[:TOP]
    idle = sorted(((v, k) for k, v in reduced["idle_by_host"].items()),
                  reverse=True)[:TOP]
    return {"device_ops": [[k, s] for s, k in ops],
            "idle_gaps": [[k, s] for s, k in idle]}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    return reduce_profile(ProfileData.from_file(path))


def describe(path: str, top: int = 15) -> str:
    """What a trace holds: planes, lines, event counts and the most
    frequent event names with their string stats (for looking at a trace
    by hand before writing code against it)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            names: dict[str, list] = {}
            for ev in line.events:
                entry = names.setdefault(ev.name, [0, 0.0, ev])
                entry[0] += 1
                entry[1] += ev.duration_ns * 1e-9
            out.append(f"  line {line.name!r}: {sum(v[0] for v in names.values())}"
                       f" events, {len(names)} names")
            for name, (n, secs, ev) in sorted(names.items(),
                                              key=lambda kv: -kv[1][1])[:top]:
                stats = {k: (v[:120] if isinstance(v, str) else v)
                         for k, v in ev.stats}
                out.append(f"    {name[:80]!r} n={n} s={secs:.6f} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    print(describe(sys.argv[1]))
    print(json.dumps(reduce_file(sys.argv[1]), default=str)[:20000])
