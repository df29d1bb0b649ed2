"""The program's view of a trace (``bench/program_trace.py``) on a
synthetic one: idle time labelled by the step loop's program spans before
other threads' and the harness's, device time per program, the share idle
while sampling, and the alignment of the trace's spans with the probe's."""
import pytest

from bench import program_trace as pt
from repro.telemetry.probes import Sample

OFF = 5.0                                  # profiler clock - harness clock


def _events():
    main, staging = "/host:CPU#0", "/host:CPU#3"
    spans = [
        ("bench.step", 10.0, 10.09), ("serve.decode", 10.0, 10.08),
        ("serve.decode.launch", 10.0, 10.001),
        ("serve.decode.wait", 10.001, 10.03),
        ("serve.decode.sample", 10.03, 10.08),
        ("bench.stamp", 10.09, 10.095), ("bench.wait", 10.095, 10.1),
        ("bench.step", 10.1, 10.2), ("serve.admit", 10.1, 10.125),
        ("serve.prefill", 10.1, 10.122),
    ]
    ev = [[n, a + OFF, b - a, main] for n, a, b in spans]
    # a staging thread's span that starts inside the sampling part
    ev.append(["tabm.stage.projector", 10.05 + OFF, 0.01, staging])
    return {
        "device": [["fusion.3", 10.0 + OFF, 0.03],
                   ["copy.1", 10.1 + OFF, 0.02]],
        "modules": [["jit_serve_cohort_b4(7)", 10.0 + OFF, 0.031],
                    ["jit_serve_prefill_b128(9)", 10.099 + OFF, 0.022]],
        "spans": ev,
    }


def _samples():
    out = []
    for n, a, b in [("serve.decode", 10.0, 10.08),
                    ("serve.decode.sample", 10.03, 10.08),
                    ("serve.prefill", 10.1, 10.122)]:
        a, b = a + 2e-6, b + 2e-6            # the probe reads its clock late
        out.append(Sample("decoder", "x", b, b - a, 0, n))
    return out


def _reduce():
    return pt.reduce_program(_events(), 10.0, 10.2, [10.0, 10.1],
                             _samples())


def test_idle_is_labelled_by_the_step_loop_first():
    out = _reduce()
    idle = dict(out["idle"])
    assert idle == pytest.approx({
        "serve.decode.sample": 0.04,
        "serve.decode.sample | tabm.stage.projector": 0.01,
        "bench.step": 0.01 + 0.075,
        "bench.stamp": 0.005, "bench.wait": 0.005,
        "serve.prefill": 0.002, "serve.admit": 0.003})
    assert out["busy_s"] == pytest.approx(0.05)
    # idle outside bench.wait: 0.145, of which program spans cover 0.055
    assert out["idle_attributed_share"] == pytest.approx(100 * 0.055 / 0.145)
    assert out["device_idle_sampling_share"] == pytest.approx(25.0)


def test_device_programs_and_alignment():
    out = _reduce()
    assert [n for n, _ in out["device_programs"]] == [
        "jit_serve_cohort_b4", "jit_serve_prefill_b128"]
    assert [s for _, s in out["device_programs"]] == pytest.approx(
        [0.031, 0.022])
    assert [op[:2] for op in out["device_ops"]] == [
        ["fusion.3", "jit_serve_cohort_b4"],
        ["copy.1", "jit_serve_prefill_b128"]]
    res = out["alignment_residual_us"]
    assert res["n"] == 3
    assert res["p50"] == pytest.approx(2.0, abs=1e-3)
    assert res["max"] == pytest.approx(2.0, abs=1e-3)


def test_no_step_span_is_an_error():
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "bench.step"]
    with pytest.raises(RuntimeError):
        pt.reduce_program(ev, 10.0, 10.2, [10.0], [])


def test_window_counters():
    s = [Sample("jit(f)", "trace", 1.0, 0.1, 0, "jit.trace"),
         Sample("jit(f)", "cache_load", 1.1, 0.01, 0, "jit.compile"),
         Sample("engine", "admit", 2.0, 0.5, 0, "serve.admit"),
         Sample("decoder", "decode", 3.0, 0.2, 4, "serve.decode"),
         Sample("decoder", "decode.sample", 3.0, 0.15, 4,
                "serve.decode.sample", True)]
    out = pt.window_counters(s, dropped=0)
    assert (out["jit_traces"], out["jit_compiles"], out["cache_loads"]) == \
        (1, 0, 1)
    assert [n for n, _ in out["longest_spans"]] == [
        "serve.admit", "serve.decode", "serve.decode.sample"]
    assert out["longest_spans"][0][1] == pytest.approx(500.0)
    spans = out["spans_ms"]
    assert set(spans) == {"serve.admit", "serve.decode",
                          "serve.decode.sample"}
    assert spans["serve.decode"] == pytest.approx([1, 200.0, 200.0])


def test_tracing_cost_is_timed_with_the_profiler_off_and_on():
    out = pt.tracing_cost_us(n=50)
    assert out["profiler_off"] > 0 and out["profiler_on"] > 0
