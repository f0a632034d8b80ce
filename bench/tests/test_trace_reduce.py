"""The trace reduction, on a small trace recorded on a v5e by
``record_trace.py``: a 2-party fit of 2 trees of depth 3 (6 histogram
kernel calls), a 50 ms host-only sleep, and 4 served waves, all inside a
``bench.window`` span."""
from pathlib import Path

import pytest

import trace_reduce
from harness import metric_reader

TRACE = Path(__file__).with_name("data") / "small.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


@pytest.fixture(scope="module")
def reduced(profile):
    return trace_reduce.reduce_profile(profile)


def _span(profile, name):
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == name:
                        return ev.start_ns, ev.duration_ns
    raise KeyError(name)


def test_window_is_the_harness_span(profile, reduced):
    _, dur = _span(profile, "bench.window")
    assert reduced["window_s"] == pytest.approx(dur * 1e-9)
    assert reduced["devices"] == 1


def test_busy_and_idle_cover_the_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = sum(reduced["idle_by_host"].values())
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-6)
    assert set(reduced["idle_by_host"]) <= {"fit", "sleep", "serve", "other"}
    # the device has nothing to do while the host sleeps 50 ms
    assert reduced["idle_by_host"]["sleep"] >= 0.045


def test_histogram_kernel_calls(reduced):
    secs, n = trace_reduce.op_seconds(reduced,
                                      _kernel_pattern())
    assert n == 2 * 3                     # one call per tree and split level
    assert 0 < secs < reduced["busy_s"]


def test_programs_and_collectives(reduced):
    runs = sum(m["n"] for m in reduced["modules"].values())
    assert runs >= 1 + 4                  # the fit and the four waves
    assert reduced["collective_s"] == 0.0  # one chip: vmap, no collective


def test_op_self_times_sum_to_busy(reduced):
    total = sum(op["s"] for op in reduced["ops"].values())
    assert total == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_breakdown(reduced):
    b = trace_reduce.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(" = " not in name for name, _ in b["device_ops"])


def test_metric_readers_on_the_trace(reduced):
    ctx = {"counters": {"jobs": 1}, "trace": reduced, "peak": None,
           "chips": 1}
    assert metric_reader("hist_kernel_s")(ctx) > 0
    assert metric_reader("collective_s")(ctx) is None
    idle = metric_reader("device_idle.fit")(ctx)
    assert 0 < idle < 100


def _kernel_pattern():
    import importlib.util
    path = Path(trace_reduce.__file__).with_name("metrics") / "hist_kernel_s.py"
    spec = importlib.util.spec_from_file_location("hk", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


def _innermost(t, spans):
    """The shortest ``bench.*`` span other than the window that covers t."""
    best = None
    for lo, hi, name in spans:
        if name != trace_reduce.WINDOW and lo <= t < hi:
            if best is None or hi - lo < best[1] - best[0]:
                best = (lo, hi, name)
    return best[2][len(trace_reduce.SPAN_PREFIX):] if best else "other"


def _nested(rng, lo, hi, depth):
    """Random properly nested spans inside [lo, hi), as one thread opens
    and closes them, some sharing an edge with a neighbour or a parent's
    start."""
    spans, t = [], lo
    while depth and t < hi:
        a = t + int(rng.integers(0, 3))
        b = min(hi, a + int(rng.integers(1, 40)))
        if a >= b:
            break
        spans.append((a, b, f"bench.s{len(spans)}_{depth}"))
        spans += _nested(rng, a, b - 1, depth - 1)     # strictly inside
        t = b
    return spans


@pytest.mark.parametrize("seed", range(4))
def test_labeller_finds_the_innermost_span(seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    spans = [(0, 400, trace_reduce.WINDOW)] + _nested(rng, 0, 400, 4)
    label_at = trace_reduce.Labeller(spans)
    for t in np.arange(-5, 410, 0.5):
        assert label_at(t) == _innermost(t, spans), t


def test_labeller_on_the_recorded_trace(profile):
    spans = trace_reduce._host_spans(profile)
    label_at = trace_reduce.Labeller(spans)
    edges = sorted({x for lo, hi, _ in spans for x in (lo, hi)})
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        assert label_at(mid) == _innermost(mid, spans)
