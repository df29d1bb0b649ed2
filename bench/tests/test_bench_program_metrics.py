"""The readers of the program's lifecycle stamps and decode parts: over a
hand-made record, over a record whose program has neither (they read
nothing), and over a tiny window of the real harness and engine."""
from types import SimpleNamespace

import pytest

from bench import registry
from bench.record import RunRecord, Span, Tracked, pct


def _req(submit, staged, admit):
    return SimpleNamespace(submit_t=submit, staged_t=staged, admit_t=admit)


def _record():
    reqs = [
        Tracked(0, 100, 64, 3, due=1.0, req=_req(1.0, 1.01, 1.06)),
        Tracked(1, 100, 64, 3, due=2.0, req=_req(2.0, 2.03, 2.05)),
        Tracked(2, 100, 64, 3, due=3.0, req=_req(3.0, 3.02, 3.1)),
        # staged, not yet admitted at the window's end
        Tracked(3, 100, 64, 3, due=3.5, req=_req(3.5, 3.52, None)),
        # due before the window: not read
        Tracked(4, 100, 64, 3, due=0.5, req=_req(0.5, 0.9, 0.95)),
    ]
    spans = [Span("decode", "decoder", 1.2, 0.1, 2),
             Span("decode.launch", "decoder", 1.11, 0.01, 2),
             Span("decode.wait", "decoder", 1.15, 0.04, 2),
             Span("decode.sample", "decoder", 1.2, 0.05, 2),
             Span("decode", "decoder", 1.5, 0.3, 1),
             Span("decode.sample", "decoder", 1.5, 0.07, 1)]
    return RunRecord(cell="x", sizes={}, peaks={}, setup_s=1.0, w0=1.0,
                     w1=4.0, requests=reqs, steps=[], spans=spans)


def _read(name, rec):
    return registry.metric_reader(name)(rec)


def test_stage_and_admission_waits():
    rec = _record()
    assert _read("stage_ms_p50", rec) == pytest.approx(
        1e3 * pct([0.01, 0.03, 0.02, 0.02], 50))
    assert _read("admit_queue_ms_p50", rec) == pytest.approx(
        1e3 * pct([0.05, 0.02, 0.08], 50))


@pytest.mark.parametrize("name", ["decode_sample_ms",
                                  "decode_sample_ms.backlog"])
def test_decode_sample_part(name):
    assert _read(name, _record()) == pytest.approx(60.0)


@pytest.mark.parametrize("name", ["stage_ms_p50", "admit_queue_ms_p50",
                                  "decode_sample_ms",
                                  "decode_sample_ms.backlog"])
def test_a_program_without_stamps_or_parts_reads_nothing(name):
    rec = _record()
    # the requests of a program that stamps submission on the wall clock
    # at construction, and nothing else, and a decode span in one piece
    for r in rec.requests:
        r.req = SimpleNamespace(submit_t=1.7e9)
    rec.spans = [s for s in rec.spans if s.phase == "decode"]
    assert _read(name, rec) is None
    rec.requests, rec.spans = [], []
    assert _read(name, rec) is None


def test_the_harness_window_feeds_the_readers():
    from bench import run
    from bench.tests.cpu_cell import tiny_cell
    harness = run.Harness(tiny_cell("open_poisson"), 7, 2.0)
    try:
        harness.warm_up()
        rec = harness.window()
    finally:
        harness.eng.shutdown()
    assert rec.due_in_window()
    for name in ("stage_ms_p50", "admit_queue_ms_p50",
                 "decode_sample_ms"):
        v = _read(name, rec)
        assert v is not None and v >= 0, name
    # the decode parts tile the whole span, step by step
    whole = rec.spans_of("decode")
    parts = [rec.spans_of(p) for p in ("decode.launch", "decode.wait",
                                        "decode.sample")]
    assert whole and all(len(p) == len(whole) for p in parts)
    assert sum(s.dt for p in parts for s in p) == pytest.approx(
        sum(s.dt for s in whole), rel=1e-9)
