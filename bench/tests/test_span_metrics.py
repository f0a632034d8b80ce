"""The readers of the program-span metrics: their arithmetic over known
span totals, and None where there is nothing to read."""
import pytest

import harness
from repro import observability
from repro.observability import TRACER

PER_JOB = {"ingest_hash_s": "ingest.hash", "ingest_align_s": "ingest.align",
           "ingest_bin_s": "ingest.bin", "fit_prepare_s": "fit.prepare",
           "fit_compile_s": "fit.compile"}
PER_WAVE = {"serve_bin_ms": "serve.bin", "serve_dispatch_ms": "serve.dispatch",
            "serve_collect_ms": "serve.collect"}
PER_REQUEST = {"serve_queue_wait_ms": "queue.wait"}
ALL = {**PER_JOB, **PER_WAVE, **PER_REQUEST}


@pytest.fixture()
def totals():
    """Fill the tracer's totals: span i of ALL gets i + 1 entries of 1.5 s."""
    TRACER.reset()
    TRACER.enable()
    try:
        for i, span in enumerate(ALL.values()):
            for _ in range(i + 1):
                TRACER.add(span, 1.5)
    finally:
        TRACER.disable()
    yield {span: 1.5 * (i + 1) for i, span in enumerate(ALL.values())}
    TRACER.reset()


def fit_ctx(jobs):
    return {"counters": {"jobs": jobs, "ingest_s": [1.0] * jobs}}


def serve_ctx(waves, requests):
    return {"counters": {"serve": {"waves": waves, "rows": 10 * waves,
                                   "bucket_rows": 32 * waves,
                                   "requests": requests}}}


def test_every_reader_is_listed_in_the_benchmark():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in ALL:
        assert listed[name]["source"] == "program_counter"
    assert {listed[n]["moves"] for n in PER_JOB} == {"fit_job_s"}
    assert {listed[n]["moves"] for n in {**PER_WAVE, **PER_REQUEST}} == {
        "serve_p50_ms"}


@pytest.mark.parametrize("name", sorted(PER_JOB))
def test_per_job_seconds(totals, name):
    read = harness.metric_reader(name)
    assert read(fit_ctx(3)) == pytest.approx(totals[PER_JOB[name]] / 3)
    assert read(fit_ctx(0)) is None
    assert read(serve_ctx(4, 9)) is None


@pytest.mark.parametrize("name", sorted(PER_WAVE))
def test_per_wave_milliseconds(totals, name):
    read = harness.metric_reader(name)
    assert read(serve_ctx(4, 9)) == pytest.approx(
        1e3 * totals[PER_WAVE[name]] / 4)
    assert read(serve_ctx(0, 9)) is None
    assert read(fit_ctx(3)) is None


def test_per_request_milliseconds(totals):
    read = harness.metric_reader("serve_queue_wait_ms")
    assert read(serve_ctx(4, 9)) == pytest.approx(1e3 * totals["queue.wait"]
                                                  / 9)
    assert read(serve_ctx(4, 0)) is None


@pytest.mark.parametrize("name", sorted(ALL))
def test_none_where_no_span_was_recorded(name):
    TRACER.reset()
    ctx = {"counters": {**fit_ctx(3)["counters"],
                        **serve_ctx(4, 9)["counters"]}}
    assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", sorted(ALL))
def test_none_on_a_program_without_span_totals(monkeypatch, totals, name):
    """A program whose tracer keeps no totals (before they existed): the
    reader returns None and does not raise."""
    monkeypatch.setattr(observability, "TRACER", object())
    ctx = {"counters": {**fit_ctx(3)["counters"],
                        **serve_ctx(4, 9)["counters"]}}
    assert harness.metric_reader(name)(ctx) is None
