"""Program spans on the profiler's clock.

The load-bearing claims:

  * a ``jax.profiler`` session arms the tracer: each span opened while it
    records is also a ``repro.<name>`` event on the host plane, nested as the
    spans nest, inside whatever encloses it there; once the session stops,
    ``span()`` is the shared no-op again and mints no ids;
  * the disabled path costs one probe call per span open;
  * per-name totals (count, seconds) and ``add`` survive threads and clear
    on ``reset``;
  * ingest -> fit -> RequestQueue serving under a session records every
    named span, one ``serve.dispatch`` per wave and one ``queue.wait`` per
    request — also when a failed collect rolls a request back and a later
    drain dispatches it again — and the session changes no fitted tree and
    no served answer;
  * ``fit.prepare`` reports the columns each party's split search
    histograms against the party's padded width, and its categorical
    columns; ``ingest.categories`` times each party's category
    dictionaries apart from its quantile binning;
  * ``ingest.align`` counts in ``prefix_ties`` the 64-bit digest prefixes
    that tied within a party or matched falsely across parties (0 on real
    ingest, the planted number on planted digests).
"""
import contextlib
import glob
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import ForestParams, PartyBlock, crypto
from repro.data import make_classification, make_party_views
from repro.federation import Federation
from repro.observability import TRACER, Tracer
from repro.observability.trace import PROFILER_PREFIX
from repro.serving import PoisonedWaveError, RequestQueue, ServeConfig

PIPELINE_SPANS = ("ingest", "ingest.hash", "ingest.align", "ingest.bin",
                  "fit.ForestParams", "fit.prepare", "fit.lower",
                  "fit.compile", "fit.run", "queue.drain", "serve.bin",
                  "serve.dispatch", "serve.collect", "queue.wait")
REQUEST_ROWS = ((1, 5, 40), (3, 70), (9,))     # one drain per group


@contextlib.contextmanager
def profiler_session(log_dir):
    """A CPU profiler session recording host events, as the benchmark's."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(log_dir) -> list[tuple[int, int, str, str]]:
    """(start ns, end ns, name, line) of every event on ``/host:CPU``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                         line.name) for ev in line.events]
    return out


def _noop_state(tracer):
    return repr(tracer._ids), len(tracer.spans()), tracer.totals()


# ------------------------------------------------------------------ tracer
def test_profiler_session_arms_tracer_and_nests_spans(tmp_path):
    TRACER.reset()
    assert not TRACER.enabled
    with profiler_session(tmp_path):
        with jax.profiler.TraceAnnotation("enclosing"):
            with TRACER.span("outer", rows=3):
                with TRACER.span("inner"):
                    pass
    try:
        totals = TRACER.totals()
        assert totals["outer"][0] == 1 and totals["inner"][0] == 1
        assert totals["outer"][1] >= totals["inner"][1] >= 0.0
        before = _noop_state(TRACER)
        assert TRACER.span("after") is TRACER.span("stop")
        assert TRACER.begin("after") is None
        TRACER.add("after", 1.0)
        assert _noop_state(TRACER) == before
    finally:
        TRACER.reset()
    ev = {name: (lo, hi, line) for lo, hi, name, line in host_events(tmp_path)
          if name in ("enclosing", "repro.outer", "repro.inner")}
    assert set(ev) == {"enclosing", "repro.outer", "repro.inner"}
    assert ev["enclosing"][2] == ev["repro.outer"][2] == ev["repro.inner"][2]
    assert ev["enclosing"][0] <= ev["repro.outer"][0] <= ev["repro.inner"][0]
    assert ev["repro.inner"][1] <= ev["repro.outer"][1] \
        <= ev["enclosing"][1]


def test_global_tracer_is_noop_without_session_or_enable():
    """The installed hook keeps the disabled path: the shared no-op, no
    ids minted, nothing buffered or counted."""
    assert TRACER._probe is not None          # installed by repro.core
    TRACER.reset()
    before = _noop_state(TRACER)
    s1, s2 = TRACER.span("a"), TRACER.span("b", rows=4)
    assert s1 is s2
    with s1:
        assert TRACER.current_context() is None
    assert TRACER.begin("c") is None
    TRACER.event("d")
    TRACER.add("e", 0.5)
    assert _noop_state(TRACER) == before


def test_one_probe_call_per_span_open():
    calls, events = [], []

    class Event:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))

        def __exit__(self, *exc):
            events.append(("close", self.name))

    recording = [False]

    def probe():
        calls.append(1)
        return recording[0]

    t = Tracer(enabled=False)
    t.set_profiler_hook(probe, Event)
    with t.span("off"):
        pass
    assert t.begin("off") is None
    assert len(calls) == 2 and events == [] and repr(t._ids) == "count(1)"
    recording[0] = True
    with t.span("outer"):
        h = t.begin("inner")
        t.finish(h)
        t.event("blip")
    assert len(calls) == 5
    assert events == [("open", "repro.outer"), ("open", "repro.inner"),
                      ("close", "repro.inner"), ("open", "repro.blip"),
                      ("close", "repro.blip"), ("close", "repro.outer")]
    assert all(name.startswith(PROFILER_PREFIX) for _, name in events)
    assert {s["name"] for s in t.spans()} == {"outer", "inner", "blip"}


def test_totals_add_and_reset_across_threads():
    t = Tracer(enabled=True)
    per_thread, n_threads = 500, 4
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(per_thread):
            with t.span("s"):
                pass
            t.add("w", 0.25)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    totals = t.totals()
    assert totals["s"][0] == n_threads * per_thread
    assert totals["w"] == (n_threads * per_thread,
                           0.25 * n_threads * per_thread)
    assert len(t.spans()) == n_threads * per_thread
    t.reset()
    assert t.totals() == {} and t.spans() == []
    t.disable()
    t.add("w", 1.0)
    assert t.totals() == {}


# ---------------------------------------------------------------- pipeline
def _pipeline():
    """Ingest shuffled party blocks, fit, and serve request groups through
    a RequestQueue; returns (trees, answers, waves served)."""
    x, y = make_classification(240, 8, seed=3)
    blocks, _, _ = make_party_views(x, y, n_parties=2, overlap=0.8, seed=3)
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(blocks, salt="spans")
    model = fed.fit(ForestParams(n_estimators=3, max_depth=3, n_bins=16,
                                 max_features=0.5, seed=5))
    server = fed.serve(model, ServeConfig(buckets=(8, 32)))
    server.warmup()
    queue = RequestQueue(server)
    pool = np.random.default_rng(7).normal(size=(200, 8))
    answers, lo = [], 0
    for group in REQUEST_ROWS:
        rids = []
        for n in group:
            rids.append(queue.submit(pool[lo:lo + n]))
            lo += n
        out = queue.drain()
        answers += [out[r] for r in rids]
    trees = jax.tree.map(np.asarray, model.trees_)
    return trees, answers, len(server.wave_stats)


@pytest.fixture(scope="module")
def pipeline_runs(tmp_path_factory):
    """The pipeline once without and once under a profiler session."""
    log_dir = tmp_path_factory.mktemp("profile")
    plain = _pipeline()
    TRACER.reset()
    try:
        with profiler_session(log_dir):
            traced = _pipeline()
        totals, spans = TRACER.totals(), TRACER.spans()
    finally:
        TRACER.reset()
    return plain, traced, totals, host_events(log_dir), spans


def test_pipeline_under_session_records_every_span(pipeline_runs):
    _, (_, _, waves), totals, events, _ = pipeline_runs
    missing = [name for name in PIPELINE_SPANS if not totals.get(name)]
    assert not missing
    assert totals["serve.dispatch"][0] == totals["serve.collect"][0] == waves
    assert totals["queue.wait"][0] == sum(len(g) for g in REQUEST_ROWS)
    assert totals["ingest.hash"][0] == totals["ingest.bin"][0] == 2
    for name in ("ingest", "ingest.align", "fit.prepare", "fit.lower",
                 "fit.compile", "fit.run"):
        assert totals[name][0] == 1, name
    assert totals["ingest"][1] >= (totals["ingest.hash"][1]
                                   + totals["ingest.align"][1]
                                   + totals["ingest.bin"][1])
    names = {name for _, _, name, _ in events}
    assert {PROFILER_PREFIX + s for s in PIPELINE_SPANS
            if s != "queue.wait"} <= names
    assert not any(n.startswith("bench.") for n in names)


def test_session_leaves_trees_and_answers_bit_identical(pipeline_runs):
    (trees_a, answers_a, waves_a), (trees_b, answers_b, waves_b), _, _, _ = \
        pipeline_runs
    for la, lb in zip(jax.tree.leaves(trees_a), jax.tree.leaves(trees_b)):
        np.testing.assert_array_equal(la, lb)
    assert waves_a == waves_b
    assert len(answers_a) == len(answers_b)
    for a, b in zip(answers_a, answers_b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_ingest_spans_count_rows_and_prefix_ties(pipeline_runs):
    """``ingest.hash`` carries each party's rows; ``ingest.align`` carries
    ``prefix_ties``, 0 where no two 64-bit digest prefixes meet."""
    *_, spans = pipeline_runs
    hashed = [s["attrs"] for s in spans if s["name"] == "ingest.hash"]
    align = [s["attrs"] for s in spans if s["name"] == "ingest.align"]
    assert len(hashed) == 2 and all(a["rows"] > 0 for a in hashed)
    assert [a["prefix_ties"] for a in align] == [0]


@pytest.mark.parametrize("a,b,ties", [
    # one equal-prefix run in each party
    ([b"P" * 8 + b"\x02" * 24, b"P" * 8 + b"\x01" * 24, b"s" * 32],
     [b"s" * 32, b"P" * 8 + b"\x01" * 24, b"P" * 8 + b"\x02" * 24], 2),
    # one false cross-party match
    ([b"Q" * 8 + b"\x03" * 24, b"s" * 32],
     [b"s" * 32, b"Q" * 8 + b"\x04" * 24], 1),
    ([b"s" * 32, b"t" * 32], [b"t" * 32], 0),
])
def test_align_ids_reports_planted_prefix_ties(a, b, ties):
    TRACER.reset()
    TRACER.enable()
    try:
        crypto.align_ids(np.array(a, "S32"), np.array(b, "S32"))
        align = [s["attrs"] for s in TRACER.spans()
                 if s["name"] == "ingest.align"]
    finally:
        TRACER.disable()
        TRACER.reset()
    assert align == [{"parties": 2, "prefix_ties": ties}]


def test_queue_wait_counted_once_after_rollback(monkeypatch):
    x, y = make_classification(120, 6, seed=4)
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(make_party_views(x, y, n_parties=2, seed=4)[0], salt="wait")
    server = fed.serve(fed.fit(ForestParams(n_estimators=2, max_depth=2,
                                            n_bins=16, seed=1)),
                       ServeConfig(buckets=(8,)))
    server.warmup()
    queue = RequestQueue(server)
    rids = [queue.submit(x[:3]), queue.submit(x[3:6])]
    collect = server.collect

    def collect_then_fail(wave):
        collect(wave)               # the wave ran; its answer is lost
        raise RuntimeError("injected collect failure")

    TRACER.reset()
    TRACER.enable()
    try:
        monkeypatch.setattr(server, "collect", collect_then_fail)
        with pytest.raises(PoisonedWaveError):
            queue.drain()
        monkeypatch.setattr(server, "collect", collect)
        out = queue.drain()
        totals = TRACER.totals()
    finally:
        TRACER.disable()
        TRACER.reset()
    assert sorted(out) == sorted(rids)
    assert totals["serve.dispatch"][0] == 2
    assert totals["queue.wait"][0] == len(rids)


@pytest.mark.parametrize("max_features,hist_cols", [(0.2, 2), (1.0, 6)])
def test_fit_prepare_reports_hist_columns(max_features, hist_cols):
    """``fit.prepare`` carries the columns each party's split search
    histograms (``hist_cols``) beside its padded width (``party_cols``):
    parties of 4 and 6 features pad to 6 columns, and ``max_features`` 0.2
    draws 2 of the 10 features a tree."""
    x, y = make_classification(120, 10, seed=6)
    blocks = [PartyBlock(name=name, x=x[:, lo:hi], ids=np.arange(len(x)),
                         y=y if name == "a" else None)
              for name, lo, hi in (("a", 0, 4), ("b", 4, 10))]
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(blocks, salt="cols")
    TRACER.reset()
    TRACER.enable()
    try:
        fed.fit(ForestParams(n_estimators=2, max_depth=2, n_bins=16,
                             max_features=max_features, seed=2))
        spans = [s for s in TRACER.spans() if s["name"] == "fit.prepare"]
    finally:
        TRACER.disable()
        TRACER.reset()
    assert len(spans) == 1
    assert spans[0]["attrs"]["hist_cols"] == hist_cols
    assert spans[0]["attrs"]["party_cols"] == 6


@pytest.mark.parametrize("categorical", [True, False])
def test_ingest_categories_span_and_cat_cols(categorical):
    """A party with a categorical column opens ``ingest.categories`` once,
    beside its ``ingest.bin``; ``fit.prepare`` counts the categorical
    columns (0, and no ``ingest.categories``, for a numeric table)."""
    n = 120
    x, y = make_classification(n, 6, seed=8)
    ids = np.arange(n)
    xa = np.empty((n, 3), object)
    xa[:, 0] = np.array(list("pqrs"))[ids % 4] if categorical else x[:, 5]
    xa[:, 1:] = x[:, :2]
    blocks = [PartyBlock(name="a", x=xa, ids=ids, y=y,
                         categorical=(0,) if categorical else ()),
              PartyBlock(name="b", x=x[:, 2:5], ids=ids)]
    fed = Federation(parties=2, n_bins=16)
    TRACER.reset()
    TRACER.enable()
    try:
        fed.ingest(blocks, salt="cats")
        fed.fit(ForestParams(n_estimators=2, max_depth=2, n_bins=16,
                             seed=2))
        totals = TRACER.totals()
        prep = [s for s in TRACER.spans() if s["name"] == "fit.prepare"]
        cats = [s for s in TRACER.spans()
                if s["name"] == "ingest.categories"]
    finally:
        TRACER.disable()
        TRACER.reset()
    assert totals["ingest.bin"][0] == 2
    assert len(prep) == 1 and prep[0]["attrs"]["cat_cols"] == int(categorical)
    if categorical:
        assert totals["ingest.categories"][0] == 1
        assert cats[0]["attrs"] == {"party": "a", "rows": n, "columns": 1}
    else:
        assert "ingest.categories" not in totals
