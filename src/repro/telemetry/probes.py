"""Wall-time probes: the ledger's dynamic (measured) population path, and
the program's spans on the profiler's timeline.

A :class:`WallProbe` collects timestamped per-brick samples from the
hot paths (``ExecutionPlan.run`` / ``produce_many``, the engine's
submit, admission, prefill and cohort-decode spans).  The collector is
deliberately host-only — ``time.monotonic`` spans and a deque append
under a short lock — so recording is legal inside the replint host-sync
hot paths (``WallProbe.record`` is itself on that list: no device syncs
may ever creep in here).

:meth:`WallProbe.span` is the one span system: it records a
:class:`Sample` and opens a ``jax.profiler.TraceAnnotation`` of the
same stable name (``serve.decode``, ``tabm.commit``, ...), so a profiler
trace shows the program's own spans beside the device's operations.
With no profiler active an annotation costs a no-op check.  A span may
be split into parts (:meth:`_Span.part`): the parts tile it, and each
part's phase is its parent's, qualified (``decode.sample`` under
``decode``), so a reader selecting a phase by equality sees whole spans
only.  :meth:`WallProbe.to_ledger` folds the ledger phases alone
(:data:`LEDGER_PHASES`): parts and the engine's other spans never change
what calibration reads.

:func:`watch_jit` registers one ``jax.monitoring`` listener for the
process: it counts jaxpr traces, backend compiles and persistent-cache
loads, and records each as a ``jit.trace`` / ``jit.compile`` span (brick
= the function's name) into every probe that asked to watch.

Measurement caveat, stated once: on asynchronous backends a span that
does not end at an existing host sync measures *dispatch*, not device
completion.  The engine's prefill and decode spans end at syncs it
already pays (the one sampling read after each decode step, the
first-token reads after prefill), so those are true wall times; the plan's per-brick
staging spans are dispatch-inclusive lower bounds, still ordered
correctly for *relative* calibration.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.telemetry.ledger import Ledger

# the phases the measured ledger folds; every other span is for readers
LEDGER_PHASES = frozenset({"stage", "prefill", "decode"})


class Sample(NamedTuple):
    """One measured span: ``t`` is ``time.monotonic()`` at its end
    (orders samples across threads), ``dt`` the measured seconds,
    ``tokens`` how many tokens the span processed, ``name`` the stable
    span name the profiler shows, ``part`` whether it is a part of a
    span (its phase then reads ``<parent phase>.<part>``), ``seq`` its
    running number in the probe."""

    brick: str
    phase: str          # stage | prefill | decode, a part, or a span kind
    t: float
    dt: float
    tokens: int
    name: str = ""
    part: bool = False
    seq: int = 0


class WallProbe:
    """Thread-safe accumulator of :class:`Sample` spans.

    The engine's staging worker threads and the step loop share one
    probe; each append takes a short lock so running numbers follow
    append order.  The bound keeps a long-running server from growing
    it without limit: a reader follows with :attr:`seq` and
    :meth:`since`, and :attr:`dropped` counts the samples the bound
    pushed out."""

    def __init__(self, maxlen: int = 65536):
        self._samples: Deque[Sample] = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._seq = 0
        self.dropped = 0

    def record(self, brick: str, phase: str, dt: float, tokens: int = 0,
               *, name: str = "", part: bool = False,
               t: Optional[float] = None) -> None:
        end = time.monotonic() if t is None else t
        with self._lock:
            if len(self._samples) == self._samples.maxlen:
                self.dropped += 1
            self._samples.append(Sample(brick, phase, end, dt, tokens,
                                        name, part, self._seq))
            self._seq += 1

    def span(self, name: str, brick: str, phase: str,
             tokens: int = 0) -> "_Span":
        """A span named ``name`` (the profiler's label too), recorded as
        ``(brick, phase)``: a context manager, or ``start()`` /
        ``end()`` where the span's end is conditional."""
        return _Span(self, name, brick, phase, tokens)

    @property
    def seq(self) -> int:
        """The running number the next sample will get."""
        return self._seq

    def since(self, seq: int) -> List[Sample]:
        """The samples still held whose running number is ``seq`` or
        later, in order; a reader that finds the first one later than
        ``seq`` lost the difference to the bound."""
        out = []
        with self._lock:
            for s in reversed(self._samples):
                if s.seq < seq:
                    break
                out.append(s)
        out.reverse()
        return out

    def samples(self) -> List[Sample]:
        with self._lock:
            return list(self._samples)

    def __len__(self) -> int:
        return len(self._samples)

    def clear(self) -> None:
        with self._lock:
            self._samples.clear()

    def to_ledger(self, meta: Optional[dict] = None) -> Ledger:
        """Fold the ledger phases' samples into a measured :class:`Ledger`
        (one record per brick/phase, ``samples`` = observation count).
        Joules stay zero — the container has no hardware PMU, so measured
        energy only enters via the fleet simulator / modeled merge;
        calibration built from this ledger corrects *latency* and falls
        back to the modeled energy term."""
        led = Ledger(meta={"source": "probe", **(meta or {})})
        for s in self.samples():
            if s.phase in LEDGER_PHASES:
                led.accumulate(s.brick, s.phase, seconds=s.dt,
                               tokens=float(s.tokens), samples=1)
        return led


class _Span:
    """One open span: a profiler annotation and, at its end, a sample.
    :meth:`part` ends the current part and begins the next; the parts
    tile the span (each begins where the one before ended, the first
    where the span began)."""

    def __init__(self, probe: WallProbe, name: str, brick: str, phase: str,
                 tokens: int):
        self.probe, self.name, self.brick, self.phase, self.tokens = (
            probe, name, brick, phase, tokens)
        self.t0: Optional[float] = None
        self._ann = None
        self._part = None                # (name, phase, annotation)
        self._mark = 0.0                 # where the current part began

    def start(self) -> "_Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = self._mark = time.monotonic()
        return self

    def part(self, name: str, phase: str) -> None:
        now = time.monotonic()
        self._end_part(now)
        ann = TraceAnnotation(name)
        ann.__enter__()
        self._part = (name, phase, ann)

    def _end_part(self, now: float, keep: bool = True) -> None:
        if self._part is not None:
            name, phase, ann = self._part
            ann.__exit__(None, None, None)
            if keep:
                self.probe.record(self.brick, phase, now - self._mark,
                                  self.tokens, name=name, part=True, t=now)
            self._part = None
            self._mark = now

    def end(self, keep: bool = True) -> None:
        """Close the span; ``keep=False`` closes it unmeasured (a failed
        operation).  A second call does nothing."""
        if self._ann is None:
            return
        now = time.monotonic()
        self._end_part(now, keep)
        self._ann.__exit__(None, None, None)
        self._ann = None
        if keep:
            self.probe.record(self.brick, self.phase, now - self.t0,
                              self.tokens, name=self.name, t=now)

    def __enter__(self) -> "_Span":
        return self.start()

    def __exit__(self, exc_type, *exc) -> bool:
        self.end(keep=exc_type is None)
        return False


# ---------------------------------------------------------------------------
# compile counters: one jax.monitoring listener for the process
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a persistent-cache hit: recorded inside the backend-compile event that
# it ends, on the same thread
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _JitWatch:
    def __init__(self):
        self.lock = threading.Lock()
        self.counts: Dict[str, int] = {"traces": 0, "compiles": 0,
                                       "cache_loads": 0}
        self.probes: "weakref.WeakSet[WallProbe]" = weakref.WeakSet()
        self.local = threading.local()

    def __call__(self, event: str, secs: float, fun_name: str = "?", **_):
        if event == _CACHE_LOAD_EVENT:
            self.local.cached = True
            return
        if event == _TRACE_EVENT:
            name, phase, key = "jit.trace", "trace", "traces"
        elif event == _COMPILE_EVENT:
            cached = getattr(self.local, "cached", False)
            self.local.cached = False
            name, phase, key = (("jit.compile", "cache_load", "cache_loads")
                                if cached else
                                ("jit.compile", "compile", "compiles"))
        else:
            return
        with self.lock:
            self.counts[key] += 1
            probes = list(self.probes)
        for p in probes:
            p.record(str(fun_name), phase, secs, name=name)


_WATCH: Optional[_JitWatch] = None
_WATCH_LOCK = threading.Lock()


def watch_jit(probe: Optional[WallProbe] = None) -> None:
    """Start counting jit traces, backend compiles and persistent-cache
    loads for the process (the listener is registered once), and record
    each into ``probe`` from now on."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = _JitWatch()
            jax.monitoring.register_event_duration_secs_listener(_WATCH)
        if probe is not None:
            _WATCH.probes.add(probe)


def jit_counts() -> Dict[str, int]:
    """Traces, compiles and cache loads counted since :func:`watch_jit`
    was first called (zeros before)."""
    if _WATCH is None:
        return {"traces": 0, "compiles": 0, "cache_loads": 0}
    with _WATCH.lock:
        return dict(_WATCH.counts)
