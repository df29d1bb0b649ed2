"""The trace reduction on a small checked-in trace: clock alignment,
busy and idle time, labelled idle gaps, and the device time of each
decode step."""
import json
import os

import pytest

from bench import registry, tracing
from bench.record import RunRecord, Step

DATA = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                   "small_trace.json")))


def _reduce():
    steps = [Step(**s) for s in DATA["steps"]]
    return tracing.reduce_events(DATA, DATA["t0"], DATA["t1"],
                                 DATA["marks"], steps), steps


def test_alignment_busy_and_idle():
    assert tracing.clock_offset(DATA["host"], DATA["marks"]) == \
        pytest.approx(-5.0)
    out, _ = _reduce()
    assert out["window_s"] == pytest.approx(0.022)
    assert out["busy_s"] == pytest.approx(0.013)
    gaps = out["breakdown"]["idle_gaps"][:3]
    assert dict(gaps) == pytest.approx(
        {"bench.stamp": 0.0035, "bench.wait": 0.0035, "bench.step": 0.002})
    assert gaps[-1][0] == "bench.step"
    top = dict(out["breakdown"]["device_ops"])
    assert sum(top.values()) == pytest.approx(0.013)


def test_union_length_merges_overlaps():
    assert tracing.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_per_step_device_time():
    out, steps = _reduce()
    assert [s["busy_s"] for s in out["steps"]] == pytest.approx(
        [0.0065, 0.0065])
    run = RunRecord(cell="x", sizes={}, peaks={}, setup_s=0, w0=9.9,
                    w1=10.1, requests=[],
                    steps=steps, spans=[], trace=out)
    assert registry.metric_reader("decode_device_ms")(run) == \
        pytest.approx(6.5)
    assert registry.metric_reader("device_idle_share")(run) == \
        pytest.approx(100 * (1 - 0.013 / 0.022))


def test_short_op_names():
    text = ('%closed_call.15 = bf16[64,896]{1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[64,896]{1,0:T(8,128)(2,1)S(1)} %fusion.90), '
            'custom_call_target="tpu_custom_call"')
    assert tracing.short_op(text) == \
        "custom-call tpu_custom_call bf16[64,896] %closed_call.15"
    assert tracing.short_op(
        "%copy.98 = bf16[24,2048]{1,0:T(8,128)} copy(bf16[24,2048]{0,1} %p)"
    ) == "copy bf16[24,2048] %copy.98"


def test_no_alignment_span_is_an_error():
    with pytest.raises(RuntimeError):
        tracing.reduce_events({"device": [], "host": []}, 0.0, 1.0, [], [])


def test_trace_takes_the_window_end():
    # the trace starts ``length`` before the window's end and stops only
    # once the window has closed, so writing it out stalls no request
    tr = tracing.Tracer(seconds=51.0)
    try:
        tr.tick(100.0)                   # the window opens at 100 s
        assert tr.length == 4.0 and tr.start_at == pytest.approx(147.0)
        assert tr.next_event(120.0) == pytest.approx(147.0)
        assert tr.t0 is None
        tr.finish()                      # never started: nothing to stop
        assert tr.t1 is None
    finally:
        os.rmdir(tr.dir)
