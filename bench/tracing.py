"""The profiler trace of a ``--trace 1`` run, and its reduction.

:class:`Tracer` takes one ``jax.profiler`` trace of the window's last few
seconds, into a temporary directory, and reads it back.  The trace stops
only once the window has closed: writing it out holds the host for
seconds, which inside the window would stall every request due then.
:func:`load_xplane` turns the ``.xplane.pb`` into plain events,
:func:`reduce_events` turns those into the numbers the metric readers
use: device busy time (the union of the intervals in which an operation
ran on the device) over the traced window, time per device operation,
the longest idle gaps labelled by what the host was doing in them (the
harness span, ``bench.*``, and the program's Python frames under it), and
the device time inside each decode step.

Times are put on the harness's clock (``time.monotonic``) through the
``bench.step`` spans: the harness notes when it opened each one, and the
trace holds the same spans on the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional

# the device plane and the line that holds one event per operation run
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
STEP_SPAN = "bench.step"


class Tracer:
    """Starts and stops one profiler trace inside the window."""

    def __init__(self, seconds: float, length: Optional[float] = None):
        self.length = length if length is not None else min(
            4.0, max(1.0, 0.4 * seconds))
        self.seconds = seconds
        self.start_at: Optional[float] = None
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.marks: List[float] = []
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def tick(self, now: float):
        """Called by the window's loop; the first call is the window's
        start, and the trace starts ``length`` seconds before its end."""
        import jax
        if self.start_at is None:
            self.start_at = now + max(0.0, self.seconds - self.length)
        if self.t0 is None and now >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self.t0 = time.monotonic()

    def next_event(self, now: float) -> float:
        if self.start_at is None:
            return now
        if self.t0 is None:
            return self.start_at
        return float("inf")

    def mark_step(self, t: float):
        if self.t0 is not None and self.t1 is None:
            self.marks.append(t)

    def finish(self):
        """Stop the trace; called once the window has closed."""
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()

    def reduce(self, record) -> dict:
        try:
            files = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if self.t0 is None or not files:
                raise RuntimeError("no profiler trace was written")
            events = load_xplane(files[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduce_events(events, self.t0, self.t1, self.marks,
                             record.steps)


PY_LINE = "python"          # the profiler's Python tracer, main thread
PY_MIN_S = 1e-4              # shorter Python frames cannot hold a long gap


def load_xplane(path: str) -> dict:
    """Plain events of a trace: ``device`` ops of the first TPU's ops
    line, the harness's ``host`` spans, and the ``python`` frames of the
    main thread that last ``PY_MIN_S`` or more; each ``[name, start_s,
    dur_s]`` on the profiler's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host, python = [], [], []
    dev_id = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if dev_id is not None and m.group(1) != dev_id:
                continue
            dev_id = m.group(1)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device += [[e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9])
                    elif line.name == PY_LINE \
                            and e.duration_ns * 1e-9 >= PY_MIN_S:
                        python.append([e.name, e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9])
    return {"device": device, "host": host, "python": python}


def clock_offset(host: list, marks: List[float]) -> Optional[float]:
    """Profiler clock minus harness clock, from the ``bench.step`` spans:
    the trace's in start order against the harness's marks."""
    steps = sorted(h[1] for h in host if h[0] == STEP_SPAN)
    n = min(len(steps), len(marks))
    if n == 0:
        return None
    diffs = sorted(s - m for s, m in zip(steps[:n], marks[:n]))
    return diffs[n // 2]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP = re.compile(r"^(%\S+) = (.+?) ([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op(text: str) -> str:
    """A device op's readable name from its HLO text: op kind, result
    shape without layouts, instruction name, and a custom call's target."""
    bare = text
    for _ in range(3):
        bare = _LAYOUT.sub("", bare)
    m = _OP.match(bare)
    if not m:
        return text[:120]
    name, shape, kind = m.groups()
    tgt = _TARGET.search(text)
    extra = f" {tgt.group(1)}" if tgt else ""
    return f"{kind}{extra} {shape[:80]} {name}"


def union_length(intervals: List[tuple]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gap_label(t: float, host: list, python: list) -> str:
    """What the host was doing at ``t``: the innermost harness span, then
    the two outermost Python frames under it that are not the harness's
    own (the program's entry points), outermost first."""
    cover = [h for h in host if h[0] <= t <= h[1]]
    if not cover:
        return "outside bench spans"
    span = max(cover)                      # the innermost: started last
    frames = [p[2] for p in python
              if span[0] <= p[0] and p[0] <= t <= p[1]
              and "run.py" not in p[2]]
    return " > ".join([span[2]] + frames[:2])


def reduce_events(events: dict, t0: float, t1: float, marks: List[float],
                  steps: list) -> dict:
    """Busy time, per-op time, labelled idle gaps and per-step device
    time of the traced window ``[t0, t1]`` (harness clock)."""
    off = clock_offset(events["host"], marks)
    if off is None:
        raise RuntimeError("the trace holds no bench.step span to align to")
    ops = []
    for name, s, d in events["device"]:
        a, b = s - off, s - off + d
        a, b = max(a, t0), min(b, t1)
        if b > a:
            ops.append((a, b, short_op(name)))
    ops.sort()
    busy = union_length([(a, b) for a, b, _ in ops])
    per_op: Dict[str, float] = {}
    for a, b, name in ops:
        per_op[name] = per_op.get(name, 0.0) + (b - a)
    host = sorted((s - off, s - off + d, name)
                  for name, s, d in events["host"])
    python = sorted((s - off, s - off + d, name)
                    for name, s, d in events.get("python", []))
    gaps, end = [], t0
    for a, b, _ in ops:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if t1 > end:
        gaps.append((end, t1))
    labelled = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        labelled.append((b - a, gap_label(mid, host, python)))
    labelled.sort(reverse=True)
    step_ops = []
    for st in steps:
        a, b = st.t - st.dt, st.t
        if a < t0 or b > t1:
            continue
        inside = [(max(s, a), min(e, b)) for s, e, _ in ops
                  if e > a and s < b]
        step_ops.append({"rows": st.rows, "bucket": st.bucket,
                         "busy_s": union_length(inside)})
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": t1 - t0,
        "busy_s": busy,
        "per_op": per_op,
        "steps": step_ops,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[label, s] for s, label in labelled[:10]],
        },
    }
