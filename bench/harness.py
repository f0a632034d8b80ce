"""The benchmark harness: resolves a cell by name and runs it once.

Everything cell-specific is found by name from ``BENCHMARK.json``:

* ``configs[].file``                 the deployment (shapes, parties, forest)
* ``bench/workloads/<cell>.json``    the cell's own parameters and limits
* ``bench/traffic/<traffic>.json``   the traffic mix; its ``kind`` names
* ``bench/traffic/<kind>.py``        the driver that runs that kind of mix
* ``bench/metrics/<metric>.py``      one reader per per-layer metric

so a later change adds a configuration, a cell, a mix, a driver or a metric
by adding files.  A driver module defines ``Driver(cell)`` with ``setup()``,
``window(seconds)``, ``release()``, ``check()``, ``end_to_end()``,
``counters()`` and the ints ``attempted``/``failed``.  A metric module
defines ``read(ctx)``, which returns a number or None when its cell has
nothing for it to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ----------------------------------------------------------------- the spec
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    params: dict
    chips: int
    end_to_end: list
    per_layer: list
    peak: dict | None = None
    seed: int = 0
    devices: list = dataclasses.field(default_factory=list)
    hist_impl: str | None = None
    clock: "CompileClock | None" = None
    root: Path = ROOT

    def annotate(self, what: str):
        """A harness span on the profiler's clock (``bench.<what>``)."""
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{what}")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    params = load_json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if params.get(key) != w[key]:
            raise ValueError(f"bench/workloads/{name}.json says {key}="
                             f"{params.get(key)!r}, BENCHMARK.json says "
                             f"{w[key]!r}")
    return Cell(
        name=name, config_name=w["config"],
        config=load_json(root / configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        params=params, chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, name)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, name)],
        root=root)


def driver_for(cell: Cell):
    kind = cell.traffic["kind"]
    return load_module(cell.root / "bench" / "traffic" / f"{kind}.py",
                       f"bench_traffic_{kind}").Driver(cell)


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")).read


# ------------------------------------------------------ compile accounting
class CompileClock:
    """Seconds of jaxpr tracing + lowering and of XLA compilation, and the
    persistent-cache hits and misses, as ``jax.monitoring`` reports them."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        self.totals = {"compile_s": 0.0, "trace_s": 0.0, "cache_hits": 0,
                       "cache_misses": 0}
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.totals[key] += secs

    def _event(self, event: str, **_kw) -> None:
        key = self.COUNTS.get(event)
        if key:
            self.totals[key] += 1

    def snapshot(self) -> dict:
        return dict(self.totals)

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


class GcClock:
    """Pauses of Python's cyclic garbage collector from its creation on, as
    ``gc.callbacks`` reports them: the number of full collections (oldest
    generation) and the longest, and the seconds of all collections."""

    def __init__(self):
        self.totals = {"gc_full": 0, "gc_full_max_s": 0.0, "gc_s": 0.0}
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        if self._t0 is None:
            return
        secs = time.perf_counter() - self._t0
        self._t0 = None
        self.totals["gc_s"] += secs
        if info["generation"] == 2:
            self.totals["gc_full"] += 1
            self.totals["gc_full_max_s"] = max(self.totals["gc_full_max_s"],
                                               secs)

    def close(self) -> dict:
        gc.callbacks.remove(self._callback)
        return dict(self.totals)


# ---------------------------------------------------------------- running
def device_info(devices) -> dict:
    import jax
    every = jax.devices()
    return {"platform": every[0].platform, "kind": every[0].device_kind,
            "count": len(every)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


@contextlib.contextmanager
def profiled(enabled: bool, keep: str | None):
    """Profile the block into a temporary directory; yields a dict that
    receives the reduced trace."""
    out: dict = {}
    if not enabled:
        yield out
        return
    import jax
    from trace_reduce import find_trace, reduce_file
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no Python call events
    opts.host_tracer_level = 1       # the harness's spans, not the runtime's
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield out
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
    try:
        t1 = time.perf_counter()
        path = find_trace(log_dir)
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(keep) / Path(path).name)
        out.update(reduce_file(path))
        print(f"trace: stop {t1 - t0:.3f} s, reduction "
              f"{time.perf_counter() - t1:.3f} s of "
              f"{Path(path).stat().st_size / 2**20:.1f} MiB", file=sys.stderr)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, keep_trace: str | None = None) -> dict:
    """Set up, measure for ``seconds``, check; returns the result record.

    The caller has already chosen the devices (``cell.devices``), the
    histogram backend and the compile cache."""
    from trace_reduce import breakdown
    cell.seed = seed
    cell.clock = cell.clock or CompileClock()
    driver = driver_for(cell)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    before, pauses = cell.clock.snapshot(), GcClock()
    with profiled(trace, keep_trace) as reduced:
        with cell.annotate("window"):
            driver.window(seconds)
        t_closed = time.perf_counter()
        in_window = CompileClock.since(before, cell.clock.snapshot())
        in_window.update(pauses.close())
    in_window["trace_cost_s"] = time.perf_counter() - t_closed
    in_window["window_s"] = t_closed - setup_s - t_start
    t_window = time.perf_counter()
    peak = memory_peak(cell.devices)
    driver.release()
    gc.collect()
    checks = driver.check()
    in_window["check_s"] = time.perf_counter() - t_window
    limits = cell.params["limits"]
    missing = sorted(set(checks) - set(limits))
    if missing:
        raise KeyError(f"bench/workloads/{cell.name}.json has no limit for "
                       f"{missing}")
    correct = all(math.isfinite(v) and v <= limits[k]
                  for k, v in checks.items())
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = {"counters": driver.counters(), "trace": reduced,
               "peak": cell.peak, "chips": cell.chips}
        values = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                values[m["name"]] = v
    else:
        values = dict(driver.end_to_end())
        values["setup_s"] = setup_s
        values = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    result = {
        "correct": bool(correct),
        "attempted": int(driver.attempted),
        "failed": int(driver.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in values.items()},
        "device": {**device_info(cell.devices), "memory_peak_bytes": peak},
    }
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = breakdown(reduced)
    result["checks"] = {k: {"value": float(v), "limit": float(limits[k])}
                        for k, v in checks.items()}
    result["_in_window"] = in_window
    return result


def print_result(result: dict) -> None:
    """Info lines, the checks on stderr (last), the result line on stdout
    (last)."""
    info = result.pop("_in_window", {})
    print(f"in window: trace_lower_s={info.get('trace_s', 0.0):.3f} "
          f"compile_s={info.get('compile_s', 0.0):.3f} "
          f"cache_hits={info.get('cache_hits', 0)} "
          f"cache_misses={info.get('cache_misses', 0)} "
          f"gc_full={info.get('gc_full', 0)} "
          f"gc_full_max_s={info.get('gc_full_max_s', 0.0):.4f} "
          f"gc_s={info.get('gc_s', 0.0):.4f}; window "
          f"{info.get('window_s', 0.0):.3f} s, trace stop + reduction "
          f"{info.get('trace_cost_s', 0.0):.3f} s, release + check "
          f"{info.get('check_s', 0.0):.3f} s",
          file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
