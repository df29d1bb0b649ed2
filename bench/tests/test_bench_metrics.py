"""Metric arithmetic over a hand-made run record: tails over every due
request with unfinished ones censored, rates over the whole window."""
import pytest

from bench import registry
from bench.record import RunRecord, Step, Tracked, pct


def _record():
    reqs = [
        # due at 1.0, first token 1.1, then 1.2, 1.4; finished
        Tracked(0, 100, 64, 3, due=1.0, stamps=[1.1, 1.2, 1.4], done=1.4,
                prefill_start=1.05),
        # due at 2.0, first token 2.5, still streaming at the end
        Tracked(1, 100, 64, 9, due=2.0, stamps=[2.5, 2.6],
                prefill_start=2.3),
        # due at 3.0, never served: censored at the window's end (4.0)
        Tracked(2, 100, 64, 9, due=3.0),
        # due before the window (backlog fill): not a TTFT sample; its
        # tokens before w0 do not count
        Tracked(3, 50, 0, 9, due=0.5, stamps=[0.6, 0.9, 1.5], done=1.5),
    ]
    return RunRecord(cell="x", sizes={}, peaks={}, setup_s=12.5, w0=1.0,
                     w1=4.0, requests=reqs,
                     steps=[Step(t=1.2, rows=2, bucket=2, context=300,
                                 dt=0.1),
                            Step(t=1.4, rows=1, bucket=1, context=150,
                                 dt=0.3)],
                     spans=[], kv_read_positions=1000)


def _read(name, rec):
    return registry.metric_reader(name)(rec)


def test_ttft_censors_unserved_requests():
    rec = _record()
    assert sorted(rec.ttft_s()) == pytest.approx([0.1, 0.5, 1.0])
    assert _read("ttft_p50_ms", rec) == pytest.approx(500.0)
    assert _read("ttft_p95_ms", rec) == pytest.approx(
        1e3 * pct([0.1, 0.5, 1.0], 95))


@pytest.mark.parametrize("name", ["itl_p95_ms", "itl_p95_ms.backlog"])
def test_itl_counts_every_gap_and_the_open_one(name):
    rec = _record()
    # r0: 0.1, 0.2; r1: 0.1 and open 4.0-2.6; r3: 0.6 (ends in window)
    assert sorted(rec.itl_s()) == pytest.approx([0.1, 0.1, 0.2, 0.6, 1.4])
    assert _read(name, rec) == pytest.approx(
        1e3 * pct([0.1, 0.1, 0.2, 0.6, 1.4], 95))


def test_rate_over_the_whole_window():
    rec = _record()
    # tokens stamped in [1, 4]: r0 3, r1 2, r3 1 (at 1.5)
    assert rec.tokens_in_window() == 6
    assert _read("output_tokens_per_s", rec) == pytest.approx(2.0)


@pytest.mark.parametrize("step", ["decode_step_ms", "decode_step_ms.backlog"])
def test_step_and_pool_readers(step):
    rec = _record()
    assert _read(step, rec) == pytest.approx(200.0)
    assert _read("cohort_rows_mean", rec) == pytest.approx(1.5)
    assert _read("kv_live_share", rec) == pytest.approx(
        100.0 * 450 / (3 * 1000))
    assert _read("admit_wait_ms_p95", rec) == pytest.approx(
        1e3 * pct([0.05, 0.3], 95))
    assert _read("setup_s", rec) == 12.5


def test_readers_find_nothing_and_say_so():
    rec = _record()
    rec.steps, rec.requests, rec.spans = [], [], []
    for name in ("decode_step_ms", "ttft_p95_ms", "itl_p95_ms",
                 "decode_step_ms.backlog", "itl_p95_ms.backlog",
                 "kv_live_share", "prefill_ms_per_group",
                 "tabm_requests_per_commit", "device_idle_share",
                 "decode_device_ms", "mfu"):
        assert _read(name, rec) is None, name


def test_mfu_counts_completed_work():
    from bench import counts
    rec = _record()
    sizes = {"hidden_size": 896, "intermediate_size": 4864,
             "num_attention_heads": 14, "num_key_value_heads": 2,
             "num_hidden_layers": 24, "vocab_size": 151936,
             "mm_hidden_size": 1152}
    rec.sizes = sizes
    rec.peaks = {"bf16_flops_per_s": 197e12}
    want = (counts.prefill_flops(sizes, 100, 64)          # r0 first token
            + counts.decode_flops(sizes, 101)
            + counts.decode_flops(sizes, 102)
            + counts.prefill_flops(sizes, 100, 64)        # r1
            + counts.decode_flops(sizes, 101)
            + counts.decode_flops(sizes, 52))             # r3 at 1.5
    assert _read("mfu", rec) == pytest.approx(100 * want / (3.0 * 197e12))
