#!/usr/bin/env python3
"""One cell's window with the profiler on, read against the program's own
spans.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

It runs the cell as ``bench/run.py`` does (set-up, warm-up, the window
with a profiler trace of its last seconds) and prints one JSON line:

* ``device_programs``: device time per compiled program, from the trace's
  module line (``jit_serve_cohort_b64``, ``jit_serve_prefill_b1024``, ...),
  and the program that ran each of the costliest device operations;
* ``idle``: the device's idle time labelled by the step loop's innermost
  program span (``serve.*``, ``tabm.*``), staging-thread spans active at
  the same moment listed after it, and a harness span (``bench.*``) only
  where no program span covers the gap; ``idle_attributed_share``, the
  share of idle time outside ``bench.wait`` that a program span covers;
  ``device_idle_sampling_share``, the share of the traced window in which
  the device is idle while the step loop is inside ``serve.decode.sample``;
* ``alignment_residual_us``: for each program span in the trace, its start
  there less its probe start, once the trace is put on the harness clock
  by the ``bench.step`` marks (``tracing.clock_offset``), p50 and max;
* ``window``: the program's counters over the window (jit traces, compiles
  and cache loads, probe samples dropped, the five longest program spans)
  and each program span's count, mean and longest, by name;
* ``metrics``: the cell's per-layer metrics, read from the same window;
* ``tracing_cost_us``: one decode step's span work (an admission span and
  a decode span in three parts), timed on this host with the profiler off
  and on, and the mean decode span before and inside the trace.

It measures on the chip only and exits nonzero without one, as
``bench/run.py`` does.  The reduction is :func:`reduce_program`, checked on
synthetic traces by ``bench/tests/test_bench_program_trace.py``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from typing import Dict, List  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry, run, tracing  # noqa: E402

PROGRAM_PREFIXES = ("serve.", "tabm.", "jit.")
MODULES_LINE = "XLA Modules"
_RUN_ID = re.compile(r"\(\d+\)$")


def load_program_trace(path: str) -> dict:
    """Plain events of a trace, each ``[name, start_s, dur_s]`` on the
    profiler's clock: the first TPU's ``device`` ops and ``modules`` (one
    per program run), and every host thread's program and harness spans
    as ``spans`` entries ``[name, start_s, dur_s, thread]``, a thread
    being the line's place in its plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, modules, spans = [], [], []
    dev_id = None
    for plane in pd.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if m:
            if dev_id is not None and m.group(1) != dev_id:
                continue
            dev_id = m.group(1)
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    dst = device
                elif line.name == MODULES_LINE:
                    dst = modules
                else:
                    continue
                dst += [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                thread = f"{plane.name}#{k}"
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES + ("bench.",)):
                        spans.append([e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, thread])
    return {"device": device, "modules": modules, "spans": spans}


def _clip(intervals, t0: float, t1: float) -> List[tuple]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def _merge(intervals) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _subtract(xs: List[tuple], ys: List[tuple]) -> List[tuple]:
    """The parts of merged ``xs`` outside merged ``ys``."""
    out = []
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
        if b > a:
            out.append((a, b))
    return out


def _overlap(xs: List[tuple], ys: List[tuple]) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _program(name: str) -> str:
    return _RUN_ID.sub("", name)


def label_at(t: float, spans: List[tuple], main: str) -> str:
    """What the host was doing at ``t``: the step loop's innermost
    program span, then the program spans of other threads open then, in
    start order; a harness span only where no program span is open."""
    open_ = [s for s in spans if s[0] <= t <= s[1]]
    prog = [s for s in open_ if not s[2].startswith("bench.")]
    mine = [s for s in prog if s[3] == main]
    others = sorted((s for s in prog if s[3] != main), key=lambda s: s[0])
    names = ([max(mine, key=_depth)[2]] if mine else []) \
        + [s[2] for s in others]
    if names:
        return " | ".join(names)
    bench = [s for s in open_ if s[3] == main]
    return max(bench, key=_depth)[2] if bench else "outside spans"


def _depth(span: tuple) -> tuple:
    """Orders nested spans outermost first: later start, then earlier
    end, is deeper."""
    return span[0], -span[1]


def reduce_program(events: dict, t0: float, t1: float, marks: List[float],
                   samples: list) -> dict:
    """The program's view of the traced window ``[t0, t1]`` (harness
    clock): device time per program, labelled idle time, the share of the
    window idle during sampling, and how far the trace's program spans
    lie from the probe's after alignment.  ``samples`` are the probe's
    :class:`~repro.telemetry.probes.Sample` of the window."""
    host = [[n, s, d] for n, s, d, _ in events["spans"]
            if n.startswith("bench.")]
    off = tracing.clock_offset(host, marks)
    if off is None:
        raise RuntimeError("the trace holds no bench.step span to align to")
    spans = sorted((s - off, s - off + d, n, th)
                   for n, s, d, th in events["spans"])
    steps = [s for s in spans if s[2] == tracing.STEP_SPAN]
    main = max({s[3] for s in steps},
               key=lambda th: sum(1 for s in steps if s[3] == th))
    ops = [(s - off, s - off + d, name) for name, s, d in events["device"]]
    busy = _merge(_clip([(a, b) for a, b, _ in ops], t0, t1))
    idle = _subtract([(t0, t1)], busy)
    window = t1 - t0

    # device time per program, and the program behind each costly op
    mods = sorted((s - off, s - off + d, _program(n))
                  for n, s, d in events["modules"])
    per_program: Dict[str, float] = {}
    for a, b, name in mods:
        for x, y in _clip([(a, b)], t0, t1):
            per_program[name] = per_program.get(name, 0.0) + (y - x)
    per_op: Dict[tuple, float] = {}
    starts = [m[0] for m in mods]
    for a, b, name in ops:
        k = bisect.bisect_right(starts, a) - 1
        prog = mods[k][2] if k >= 0 and mods[k][1] >= b else "?"
        for x, y in _clip([(a, b)], t0, t1):
            key = (tracing.short_op(name), prog)
            per_op[key] = per_op.get(key, 0.0) + (y - x)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]

    # idle time by label, at the midpoint of each idle interval's pieces
    cuts = sorted({p for s in spans for p in s[:2] if t0 < p < t1})
    by_label: Dict[str, float] = {}
    for a, b in idle:
        lo = bisect.bisect_right(cuts, a)
        hi = bisect.bisect_left(cuts, b)
        edges = [a] + cuts[lo:hi] + [b]
        for x, y in zip(edges, edges[1:]):
            if y > x:
                lab = label_at(0.5 * (x + y), spans, main)
                by_label[lab] = by_label.get(lab, 0.0) + (y - x)
    waits = _merge(_clip([(s[0], s[1]) for s in spans
                          if s[2] == "bench.wait" and s[3] == main], t0, t1))
    prog_cover = _merge(_clip([(s[0], s[1]) for s in spans
                               if not s[2].startswith("bench.")], t0, t1))
    idle_open = _subtract(idle, waits)
    idle_open_s = sum(b - a for a, b in idle_open)
    covered = _overlap(idle_open, prog_cover)
    sampling = _merge(_clip([(s[0], s[1]) for s in spans
                             if s[2] == "serve.decode.sample"
                             and s[3] == main], t0, t1))

    # alignment: each program span of the trace against the probe's
    residual = []
    probe: Dict[str, List[float]] = {}
    for s in samples:
        if s.name:
            probe.setdefault(s.name, []).append(s.t - s.dt)
    for v in probe.values():
        v.sort()
    for a, _b, name, _th in spans:
        got = probe.get(name)
        if not got or not t0 <= a <= t1:
            continue
        k = bisect.bisect_left(got, a)
        near = min((got[i] for i in (k - 1, k) if 0 <= i < len(got)),
                   key=lambda p: abs(p - a))
        residual.append(abs(a - near) * 1e6)
    residual.sort()
    return {
        "window_s": window,
        "busy_s": sum(b - a for a, b in busy),
        "device_programs": sorted(([n, s] for n, s in per_program.items()),
                                  key=lambda x: -x[1]),
        "device_ops": [[op, prog, s] for (op, prog), s in top_ops],
        "idle": sorted(([n, s] for n, s in by_label.items()),
                       key=lambda x: -x[1])[:20],
        "idle_attributed_share": (100.0 * covered / idle_open_s
                                  if idle_open_s > 0 else None),
        "device_idle_sampling_share": 100.0 * _overlap(idle, sampling)
        / window if window > 0 else None,
        "alignment_residual_us": {
            "n": len(residual),
            "p50": residual[len(residual) // 2] if residual else None,
            "max": residual[-1] if residual else None},
    }


def window_counters(samples: list, dropped: int) -> dict:
    """The program's counters over the window, from its probe samples."""
    jit = Counter(s.phase for s in samples if s.name.startswith("jit."))
    prog = [s for s in samples if s.name.startswith(PROGRAM_PREFIXES)
            and not s.name.startswith("jit.")]
    longest = sorted(prog, key=lambda s: -s.dt)[:5]
    by_name: Dict[str, List[float]] = {}
    for s in prog:
        by_name.setdefault(s.name, []).append(s.dt)
    return {
        "jit_traces": jit["trace"], "jit_compiles": jit["compile"],
        "cache_loads": jit["cache_load"], "spans_dropped": dropped,
        "longest_spans": [[s.name, 1e3 * s.dt] for s in longest],
        "spans_ms": {n: [len(v), 1e3 * statistics.fmean(v), 1e3 * max(v)]
                     for n, v in sorted(by_name.items())},
    }


def tracing_cost_us(n: int = 20000) -> dict:
    """One decode step's span work on this host, per step, with the
    profiler off and on."""
    import tempfile

    import jax
    from repro.telemetry.probes import WallProbe

    def steps():
        probe = WallProbe(maxlen=8)
        t = time.perf_counter()
        for _ in range(n):
            with probe.span("serve.admit", "engine", "admit"):
                pass
            sp = probe.span("serve.decode", "decoder", "decode",
                            tokens=64).start()
            sp.part("serve.decode.launch", "decode.launch")
            sp.part("serve.decode.wait", "decode.wait")
            sp.part("serve.decode.sample", "decode.sample")
            sp.end()
        return 1e6 * (time.perf_counter() - t) / n

    off = steps()
    d = tempfile.mkdtemp(prefix="bench-cost-")
    jax.profiler.start_trace(d)
    try:
        on = steps()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"profiler_off": off, "profiler_on": on}


class _KeepTrace(tracing.Tracer):
    """The benchmark's tracer, reading the program's events as well
    before its directory goes."""

    def reduce(self, record) -> dict:
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        self.program_events = load_program_trace(files[-1]) if files \
            else None
        return super().reduce(record)


def profile_cell(cell, seed: int, seconds: float, peaks: dict) -> dict:
    import jax
    harness = run.Harness(cell, seed, seconds)
    jax.monitoring.register_event_duration_secs_listener(harness.on_compile)
    try:
        harness.warm_up()
        tracer = _KeepTrace(seconds)
        setup_s = time.monotonic() - T_START
        probe = harness.eng.probe
        seq0, dropped0 = probe.seq, probe.dropped
        record = harness.window(tracer)
    finally:
        jax.monitoring.unregister_event_duration_listener(harness.on_compile)
    samples = probe.since(seq0)
    lost = (samples[0].seq - seq0) if samples else 0
    std = record.trace = tracer.reduce(record)
    record.peaks = peaks
    if tracer.program_events is None:
        raise run.BenchError("no profiler trace was written")
    prog = reduce_program(tracer.program_events, tracer.t0, tracer.t1,
                          tracer.marks, samples)
    before = [s.dt for s in samples
              if s.phase == "decode" and s.t < tracer.t0]
    inside = [s.dt for s in samples
              if s.phase == "decode" and s.t - s.dt >= tracer.t0]
    harness.eng.shutdown()
    cost = tracing_cost_us()
    cost["decode_span_ms_before_trace"] = (
        1e3 * statistics.fmean(before) if before else None)
    cost["decode_span_ms_in_trace"] = (
        1e3 * statistics.fmean(inside) if inside else None)
    return {
        "cell": cell.name, "seed": seed, "setup_s": setup_s,
        "metrics": run.read_metrics(cell.per_layer, record, cell.bench_dir),
        "device_idle_share": 100.0 * (1.0 - std["busy_s"] / std["window_s"]),
        "decode_device_ms": (1e3 * statistics.fmean(
            s["busy_s"] for s in std["steps"]) if std["steps"] else None),
        "harness_idle_gaps": std["breakdown"]["idle_gaps"],
        **prog,
        "window": {**window_counters(samples,
                                     probe.dropped - dropped0 + lost),
                   "compiles_in_window": harness.compiles,
                   "probe_samples_total": probe.seq,
                   "probe_maxlen": probe._samples.maxlen},
        "tracing_cost_us": cost,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the JSON to a file here")
    args = ap.parse_args(argv)
    try:
        cell = registry.find_cell(args.workload)
        run._import_program()
        run.use_compile_cache()
        _, peaks = run.check_device(cell)
        result = profile_cell(cell, args.seed, args.seconds, peaks)
    except (run.BenchError, registry.UnknownName, OSError) as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out,
                               f"{args.workload}-{args.seed}.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
