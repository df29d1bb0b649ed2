"""Telemetry subsystem (PR 8): ledger arithmetic + persistence, the
measured->scheduler calibration feedback, wall-time probes on the
plan/engine hot paths, and the fleet-scale battery simulator (incl.
PMU/PowerPolicy replay from a recorded fleet trace)."""
import dataclasses
import time

import numpy as np
import pytest

import jax
from hypothesis import given, strategies as hst

from repro.configs import get_config
from repro.core.bricks import decompose
from repro.core.power import PowerPolicy, PowerState
from repro.core.scheduler import (brick_cost, edge_accelerators,
                                  kv_block_budgets, schedule)
from repro.core.tabm import SlotClassPool
from repro.launch.steps import init_params
from repro.serving.engine import Request, ServingEngine, TraceEvent
from repro.telemetry import CostCalibration, Ledger, PhaseRecord, WallProbe
from repro.telemetry.fleet import (FleetSimulator, ModalityProfile,
                                   replay_trace)


def _graph(arch="llava-onevision-0.5b"):
    g = decompose(get_config(arch))
    g.bricks = [dataclasses.replace(
        b, param_bytes=max(1, int(b.flops_per_token)))
        for b in g.bricks]
    return g


# ---------------------------------------------------------------------------
# ledger arithmetic + persistence
# ---------------------------------------------------------------------------

def test_phase_record_algebra():
    a = PhaseRecord(flops=10, bytes=4, tokens=2, joules=1.0, seconds=0.5,
                    samples=1)
    b = PhaseRecord(flops=30, bytes=6, tokens=2, joules=3.0, seconds=0.5,
                    samples=2)
    s = a + b
    assert (s.flops, s.bytes, s.tokens, s.samples) == (40, 10, 4, 3)
    assert s.j_per_token == pytest.approx(1.0)
    assert s.tokens_per_s == pytest.approx(4.0)
    d = a * 3
    assert d.flops == 30 and d.tokens == 6
    assert d.samples == 1, "samples is a count, not an extensive quantity"
    assert PhaseRecord().j_per_token == 0.0   # no division by zero


def test_ledger_accumulate_merge_scale_roundtrip(tmp_path):
    led = Ledger()
    led.accumulate("decoder", "decode", seconds=1.0, tokens=10, joules=2.0,
                   samples=1)
    led.accumulate("decoder", "decode", seconds=1.0, tokens=10, samples=1)
    assert led.record("decoder", "decode").tokens == 20
    assert led.record("decoder", "decode").samples == 2

    other = Ledger(meta={"bench": "x"})
    other.accumulate("projector", "stage", seconds=0.5, tokens=100,
                     samples=3)
    merged = led + other
    assert len(merged) == 2 and len(led) == 1   # __add__ does not mutate
    led.merge(other)
    assert len(led) == 2 and led.meta["bench"] == "x"

    half = led.scale(0.5)
    assert half.record("decoder", "decode").tokens == 10
    assert half.record("decoder", "decode").samples == 2

    path = tmp_path / "ledger.json"
    led.save(str(path))
    back = Ledger.load(str(path))
    assert back.to_dict() == led.to_dict()
    with pytest.raises(ValueError):
        led.accumulate("decoder", "warmup", tokens=1)


def test_ledger_total_uses_phase_token_max_rule():
    """Bricks chain: embed/decoder/head all see the SAME decode stream,
    so phase tokens aggregate by max, while seconds/joules add."""
    led = Ledger()
    for brick in ("embed", "decoder", "head"):
        led.accumulate(brick, "decode", seconds=1.0, tokens=50, joules=1.0)
    tot = led.total("decode")
    assert tot.tokens == 50
    assert tot.seconds == pytest.approx(3.0)
    assert tot.joules == pytest.approx(3.0)
    assert led.j_per_token("decode") == pytest.approx(3.0 / 50)


@given(recs=hst.lists(
    hst.tuples(hst.integers(1, 100), hst.integers(1, 100),
               hst.integers(0, 5)), min_size=1, max_size=8))
def test_ledger_merge_linear_property(recs):
    """Property (hypothesis): folding records one-by-one equals one
    bulk-merged ledger, and JSON round-trip preserves it exactly."""
    one = Ledger()
    parts = []
    for tok, sec, n in recs:
        part = Ledger()
        part.accumulate("b", "decode", tokens=tok, seconds=sec, samples=n)
        parts.append(part)
        one.accumulate("b", "decode", tokens=tok, seconds=sec, samples=n)
    bulk = Ledger()
    for p in parts:
        bulk.merge(p)
    assert bulk.to_dict() == one.to_dict()
    assert Ledger.from_dict(one.to_dict()).to_dict() == one.to_dict()


def test_ledger_modeled_from_cost_model():
    """Static population: compile-time roofline+energy rows, samples==0."""
    g = _graph()
    accels = edge_accelerators()
    pl = schedule(g, accels, n_tokens=64, objective="energy")
    by_name = {a.name: a for a in accels}
    accel_for = {b: by_name[a] for b, a in pl.assignment.items()}
    led = Ledger.modeled(g, accel_for, phase_tokens={
        "stage": 729, "prefill": 64, "decode": 1})
    assert len(led) > 0 and led.meta["source"] == "modeled"
    for _brick, _phase, rec in led.items():
        assert rec.samples == 0, "modeled rows must not look measured"
        assert rec.seconds > 0 and rec.joules > 0
    # decoder-side bricks never appear in the stage phase and vice versa
    phases_of = {}
    for brick, phase, _ in led.items():
        phases_of.setdefault(brick, set()).add(phase)
    assert "decode" not in phases_of.get("projector", set())
    assert "stage" not in phases_of.get("decoder", set())
    # and a profile built from it prices every phase
    prof = ModalityProfile.from_ledger(led)
    assert all(prof.j_per_token[p] > 0 for p in ("stage", "prefill",
                                                 "decode"))


# ---------------------------------------------------------------------------
# calibration: measured overrides modeled
# ---------------------------------------------------------------------------

def test_calibration_observe_lookup_fallback_roundtrip(tmp_path):
    cal = CostCalibration(prior=4)
    assert not cal and cal.sample("decoder", "rk-gpu") is None
    cal.observe("decoder", None, seconds=2.0, tokens=100, n=2)
    # profile-agnostic fallback: exact key misses, (brick, None) hits
    s = cal.sample("decoder", "rk-gpu")
    assert s is not None and s.seconds_per_token == pytest.approx(0.02)
    cal.observe("decoder", "rk-gpu", seconds=1.0, tokens=100, joules=5.0)
    exact = cal.sample("decoder", "rk-gpu")
    assert exact.seconds_per_token == pytest.approx(0.01)
    assert cal.weight(0) == 0.0 and cal.weight(4) == pytest.approx(0.5)
    assert cal.weight(4000) > 0.99
    # energy pressure: measured/modeled J per token; 1.0 with no joules
    assert cal.energy_pressure("decoder", None, 1.0) == 1.0
    assert cal.energy_pressure("decoder", "rk-gpu", 0.025) == pytest.approx(
        2.0)
    path = tmp_path / "cal.json"
    cal.save(str(path))
    back = CostCalibration.load(str(path))
    assert back.to_dict() == cal.to_dict()


def test_calibration_from_ledger_skips_modeled_rows():
    led = Ledger()
    led.accumulate("decoder", "decode", seconds=1.0, tokens=10, samples=2)
    led.accumulate("embed", "decode", seconds=9.0, tokens=10, samples=0)
    cal = CostCalibration.from_ledger(led)
    assert cal.sample("decoder") is not None
    assert cal.sample("embed") is None, "samples==0 rows are predictions"


def test_brick_cost_calibrated_vs_modeled():
    g = _graph()
    acc = next(a for a in edge_accelerators() if a.name == "gpu")
    brick = g.brick("decoder")
    base = brick_cost(brick, acc, 64)
    # empty table: calibration is a no-op
    assert brick_cost(brick, acc, 64,
                      calibration=CostCalibration()).latency_s == \
        base.latency_s
    # a disagreeing measurement changes the cost...
    cal = CostCalibration(prior=4)
    slow = base.latency_s / 64 * 10            # 10x slower per token
    cal.observe("decoder", acc.profile.name, seconds=slow * 640,
                tokens=640, n=4)
    mixed = brick_cost(brick, acc, 64, calibration=cal)
    assert mixed.latency_s > base.latency_s
    # ...blended at n==prior exactly halfway...
    assert mixed.latency_s == pytest.approx(
        0.5 * base.latency_s + 0.5 * slow * 64, rel=1e-9)
    # ...and measurement dominates at large n
    cal2 = CostCalibration(prior=4)
    cal2.observe("decoder", acc.profile.name, seconds=slow * 640,
                 tokens=640, n=4000)
    assert brick_cost(brick, acc, 64,
                      calibration=cal2).latency_s == pytest.approx(
        slow * 64, rel=1e-2)
    # energy stays modeled when the sample carries no joules
    assert mixed.energy_j == pytest.approx(base.energy_j)
    # infeasible stays infeasible regardless of observations
    npu = next(a for a in edge_accelerators() if a.name == "npu")
    dyn = dataclasses.replace(brick, static_shape=False)
    cal3 = CostCalibration()
    cal3.observe(dyn.name, npu.profile.name, seconds=1e-9, tokens=1e6,
                 n=10_000)
    assert not brick_cost(dyn, npu, 64, calibration=cal3).feasible


def test_schedule_placement_flips_under_calibration():
    """The DP prices from observation: a brick measured pathologically
    slow on its modeled-best unit migrates off it."""
    g = _graph()
    accels = edge_accelerators()
    base = schedule(g, accels, 256, "latency")
    victim = "decoder"
    home = base.assignment[victim]
    prof = next(a for a in accels if a.name == home).profile.name
    cal = CostCalibration(prior=1)
    cal.observe(victim, prof, seconds=1e4, tokens=1.0, n=10_000)
    moved = schedule(g, accels, 256, "latency", calibration=cal)
    assert moved.assignment[victim] != home, (
        f"{victim} stayed on {home} despite measured 1e4 s/token")
    # untouched table reproduces the modeled placement
    assert schedule(g, accels, 256, "latency",
                    calibration=CostCalibration()).assignment == \
        base.assignment


def test_kv_budgets_tighten_under_energy_pressure():
    cfg = get_config("llava-onevision-0.5b").reduced()
    pool = SlotClassPool.from_config(cfg, slots_per_class=2)
    names = list(pool.classes)                 # ascending by slab size
    calm = kv_block_budgets(pool, 100, {}, kv_scale=1.0)
    hot = kv_block_budgets(pool, 100, {}, kv_scale=1.0,
                           energy_pressure=2.0)
    assert hot[names[-1]] < calm[names[-1]], (
        "hotter-than-modeled decode must shed hi-res KV grants earlier")
    assert hot[names[0]] == calm[names[0]] == 100, (
        "the thumbnail class keeps the pool (hi-res sheds first)")
    # better-than-modeled energy never RELAXES beyond the battery knob
    cool = kv_block_budgets(pool, 100, {}, kv_scale=0.5,
                            energy_pressure=0.25)
    assert cool == kv_block_budgets(pool, 100, {}, kv_scale=0.5)


# ---------------------------------------------------------------------------
# probes + engine/plan integration
# ---------------------------------------------------------------------------

def test_wall_probe_record_and_to_ledger():
    probe = WallProbe()
    probe.record("decoder", "decode", 0.25, tokens=4)
    with probe.span("tabm.stage.projector", "projector", "stage", tokens=8):
        pass
    assert len(probe) == 2
    ts = [s.t for s in probe.samples()]
    assert ts == sorted(ts), "monotonic stamps order samples"
    led = probe.to_ledger(meta={"collector": "test"})
    rec = led.record("decoder", "decode")
    assert rec.seconds == pytest.approx(0.25) and rec.tokens == 4
    assert rec.samples == 1 and rec.joules == 0.0
    assert led.record("projector", "stage").samples == 1
    probe.clear()
    assert len(probe) == 0


def test_sub_spans_leave_ledger_and_calibration_unchanged():
    def fill(probe, parts):
        probe.record("projector", "stage", 0.01, tokens=8)
        for dt in (0.2, 0.3):
            with probe.span("serve.decode", "decoder", "decode",
                            tokens=4) as sp:
                if parts:
                    sp.part("serve.decode.launch", "decode.launch")
                    sp.part("serve.decode.wait", "decode.wait")
                    sp.part("serve.decode.sample", "decode.sample")
            # the whole span's time, as the test sets it
            probe._samples[-1] = probe._samples[-1]._replace(dt=dt)
        if parts:
            with probe.span("serve.admit", "engine", "admit"):
                pass
            probe.record("fn", "compile", 1.5, name="jit.compile")

    plain, split = WallProbe(), WallProbe()
    fill(plain, parts=False)
    fill(split, parts=True)
    assert len(split) > len(plain)
    parts = [s for s in split.samples() if s.part]
    assert {s.phase for s in parts} == {"decode.launch", "decode.wait",
                                        "decode.sample"}
    assert all(s.brick == "decoder" and s.tokens == 4 for s in parts)
    assert split.to_ledger().to_dict() == plain.to_ledger().to_dict()
    a = CostCalibration.from_ledger(plain.to_ledger(), prior=1)
    b = CostCalibration.from_ledger(split.to_ledger(), prior=1)
    assert a.sample("decoder") == b.sample("decoder")
    assert a.sample("projector") == b.sample("projector")


def test_span_parts_tile_the_span():
    probe = WallProbe()
    with probe.span("serve.decode", "decoder", "decode", tokens=2) as sp:
        sp.part("serve.decode.launch", "decode.launch")
        sp.part("serve.decode.wait", "decode.wait")
        sum(range(10000))
        sp.part("serve.decode.sample", "decode.sample")
    *parts, whole = probe.samples()
    assert [s.name for s in parts] == ["serve.decode.launch",
                                       "serve.decode.wait",
                                       "serve.decode.sample"]
    assert whole.name == "serve.decode" and not whole.part
    assert sum(s.dt for s in parts) == pytest.approx(whole.dt, abs=1e-9)
    assert parts[0].t - parts[0].dt == pytest.approx(whole.t - whole.dt,
                                                     abs=1e-9)
    assert parts[-1].t == whole.t
    # a span that ends unmeasured (a failed operation) records nothing
    with pytest.raises(ValueError):
        with probe.span("serve.submit", "engine", "submit"):
            raise ValueError
    sp = probe.span("serve.prefill", "decoder", "prefill").start()
    sp.end(keep=False)
    sp.end()
    assert len(probe) == 4


def test_since_and_dropped_across_an_overflow():
    probe = WallProbe(maxlen=4)
    assert probe.seq == 0 and probe.since(0) == []
    for i in range(3):
        probe.record("b", "decode", 0.1 * i)
    cursor = probe.seq
    assert cursor == 3 and probe.dropped == 0
    assert [s.seq for s in probe.since(1)] == [1, 2]
    for i in range(3):
        probe.record("b", "prefill", 0.1 * i)
    # six appended into four places: the two oldest pushed out
    assert probe.dropped == 2 and len(probe) == 4
    assert [s.seq for s in probe.since(cursor)] == [3, 4, 5]
    assert [s.seq for s in probe.since(0)] == [2, 3, 4, 5]
    assert probe.since(0)[0].seq > 0, "a reader behind the bound sees loss"
    assert probe.since(probe.seq) == []
    probe.clear()
    assert probe.dropped == 2 and probe.seq == 6 and probe.since(0) == []


def test_program_spans_show_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    probe = WallProbe()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with probe.span("serve.decode", "decoder", "decode") as sp:
            sp.part("serve.decode.launch", "decode.launch")
            jax.numpy.ones(4).block_until_ready()
            sp.part("serve.decode.sample", "decode.sample")
        with probe.span("tabm.commit", "tabm", "commit"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert {"serve.decode", "serve.decode.launch", "serve.decode.sample",
            "tabm.commit"} <= names
    assert len(probe) == 4


def test_jit_watch_counts_and_records_spans():
    from repro.telemetry import probes
    probe = WallProbe()
    probes.watch_jit(probe)
    before = probes.jit_counts()

    def fresh_program(x):
        return x * 3 + 1

    jax.jit(fresh_program)(jax.numpy.arange(3.0)).block_until_ready()
    after = probes.jit_counts()
    assert after["traces"] > before["traces"]
    assert (after["compiles"] + after["cache_loads"]
            > before["compiles"] + before["cache_loads"])
    spans = [s for s in probe.samples() if "fresh_program" in s.brick]
    assert {s.name for s in spans} == {"jit.trace", "jit.compile"}
    assert {s.phase for s in spans} <= {"trace", "compile", "cache_load"}
    assert probe.to_ledger().to_dict() == WallProbe().to_ledger().to_dict()


@pytest.mark.parametrize("async_staging", [False, True])
def test_request_lifecycle_stamps_are_monotonic_and_ordered(async_staging):
    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, tokens=np.arange(6 + i) + 3, max_new_tokens=3,
                    vision_feats=rng.standard_normal(
                        (1, cfg.vision_tokens, cfg.vision_feat_dim)
                    ).astype(np.float32) * 0.02)
            for i in range(3)]
    assert all(r.submit_t is None for r in reqs), "stamped at submit"
    with ServingEngine(cfg, params, n_slots=2, max_len=128,
                       async_staging=async_staging) as eng:
        t0 = time.monotonic()
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        t1 = time.monotonic()
        assert len(done) == 3 and all(r.error is None for r in done)
        for r in done:
            stamps = [r.submit_t, r.staged_t, r.admit_t, r.first_token_mt,
                      r.finish_mt]
            assert None not in stamps
            assert t0 <= stamps[0] and stamps[-1] <= t1
            assert stamps == sorted(stamps), stamps
            # the wall-clock views follow the monotonic stamps
            assert r.finish_t - r.first_token_t == pytest.approx(
                r.finish_mt - r.first_token_mt)
            assert r.e2e_latency == pytest.approx(r.finish_mt - r.submit_t)
        assert eng.stats.start_t is not None and eng.stats.start_t >= t0
        assert eng.stats.tokens_per_s() > 0
        names = {s.name for s in eng.probe.samples()}
        assert {"serve.submit", "serve.admit", "serve.prefill",
                "serve.decode", "serve.decode.launch", "serve.decode.wait",
                "serve.decode.sample", "tabm.acquire", "tabm.commit",
                "tabm.wait_ready"} <= names
        assert any(n.startswith("tabm.stage.") for n in names)
        # the decode parts tile each decode span
        samples = eng.probe.samples()
        whole = [s for s in samples if s.phase == "decode"]
        for phase in ("decode.launch", "decode.wait", "decode.sample"):
            assert len([s for s in samples if s.phase == phase]) == \
                len(whole)
        for w in whole:
            parts = [s for s in samples if s.part and s.t <= w.t
                     and s.t - s.dt >= w.t - w.dt - 1e-9]
            assert sum(s.dt for s in parts) == pytest.approx(w.dt,
                                                             abs=1e-9)
        # the per-step trace event is gone; the cohort event stays
        events = {e for e, _r, _t in eng.trace}
        assert "decode_cohort" in events and "decode_step" not in events
        assert set(eng.jit_counts()) == {"traces", "compiles",
                                         "cache_loads"}


def test_engine_probes_and_monotonic_trace():
    """One synchronous engine run populates measured prefill/decode (and
    vision staging) ledger rows, the trace is TraceEvent-typed with
    nondecreasing monotonic stamps, and the measured calibration is
    consumable by the scheduler."""
    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=np.arange(6 + i) + 3, max_new_tokens=3,
                    vision_feats=rng.standard_normal(
                        (1, cfg.vision_tokens, cfg.vision_feat_dim)
                    ).astype(np.float32) * 0.02)
            for i in range(2)]
    with ServingEngine(cfg, params, n_slots=2, max_len=128,
                       async_staging=False) as eng:
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == 2 and all(r.error is None for r in done)
        led = eng.measured_ledger()
        assert led.record("decoder", "decode").samples > 0
        assert led.record("decoder", "decode").tokens > 0
        assert led.record("decoder", "prefill").samples > 0
        # the plan probe contributed vision-side staging spans too
        assert any(phase == "stage" for _b, phase, _r in led.items())
        events = list(eng.trace)
        assert events and all(isinstance(e, TraceEvent) for e in events)
        # satellite: timestamps are time.monotonic(), nondecreasing in
        # append order on this single-threaded run
        stamps = [e.t for e in events]
        assert stamps == sorted(stamps)
        # legacy tuple-unpacking consumers keep working
        assert all(isinstance(e.rid, int) for e in events)
        for ev, _rid, _t in events:
            assert isinstance(ev, str)
        cal = eng.measured_calibration()
        assert cal and cal.sample("decoder") is not None
        # measured-latency feedback prices differently from the pure model
        g = _graph()
        acc = next(a for a in edge_accelerators() if a.name == "gpu")
        assert brick_cost(g.brick("decoder"), acc, 8,
                          calibration=cal).latency_s != \
            brick_cost(g.brick("decoder"), acc, 8).latency_s


# ---------------------------------------------------------------------------
# fleet simulator + trace replay
# ---------------------------------------------------------------------------

def _small_fleet(**kw):
    kw.setdefault("battery_mah", 150.0)
    kw.setdefault("dt_s", 10.0)
    return FleetSimulator(120, ModalityProfile.default_edge(), seed=7, **kw)


def test_fleet_deterministic_and_traverses_all_states():
    rep1 = _small_fleet().run(2.0)
    rep2 = _small_fleet().run(2.0)
    assert rep1.tokens_per_s == rep2.tokens_per_s
    assert rep1.j_per_token == rep2.j_per_token
    assert np.array_equal(rep1.survival_hours, rep2.survival_hours)
    assert rep1.n_devices == 120 and rep1.j_per_token > 0
    assert rep1.states_seen == {s.value for s in PowerState}
    assert all(rep1.state_ticks[s] > 0 for s in rep1.states_seen)
    assert rep1.dead > 0 and rep1.survival_hours_p50 <= rep1.hours
    assert rep1.shed_tokens > 0, "throttling/cascade must shed load"
    counts, _edges = rep1.histogram()
    assert counts.sum() == rep1.n_devices
    assert "tokens/s" in rep1.summary()
    with pytest.raises(ValueError):
        FleetSimulator(0, ModalityProfile.default_edge())


def test_fleet_seed_changes_fleet():
    a = FleetSimulator(50, ModalityProfile.default_edge(), seed=1,
                       battery_mah=150.0, dt_s=10.0).run(1.0)
    b = FleetSimulator(50, ModalityProfile.default_edge(), seed=2,
                       battery_mah=150.0, dt_s=10.0).run(1.0)
    assert a.tokens_per_s != b.tokens_per_s


def test_fleet_trace_replays_through_fresh_pmu_policy():
    """PMU/PowerPolicy transitions are a pure function of the drain
    history: re-driving the recorded per-tick joules through FRESH
    instances reproduces every recorded state and charge level."""
    sim = _small_fleet(record_trace=True)
    sim.run(1.5)
    events = list(sim.trace)
    assert events, "trace recording produced nothing"
    assert {e.state for e in events} == {s.value for s in PowerState}
    replayed = replay_trace(events, battery_mah=150.0,
                            policy=PowerPolicy())
    per_dev = {}
    for e in events:
        per_dev.setdefault(e.device, []).append(e)
    for dev, evs in per_dev.items():
        got = replayed[dev]
        assert len(got) == len(evs)
        for (state, level), ev in zip(got, evs):
            assert state == ev.state, (dev, ev)
            assert level == pytest.approx(ev.level, abs=1e-12)
