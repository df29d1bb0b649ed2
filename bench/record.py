"""What one run saw, kept for the metric readers (``bench/metrics``).

Every time here is ``time.monotonic()`` seconds, stamped by the harness:
a request's due time, and each of its tokens as the harness first saw it,
right after the engine step that produced it returned (the engine's step
ends at host syncs, so a stamp is accurate to the step).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Tracked:
    """One offered request."""

    idx: int
    prompt_len: int
    n_patches: int
    max_new: int
    due: float                                  # when it was due
    stamps: List[float] = field(default_factory=list)
    done: Optional[float] = None                # stamp of its end
    failed: bool = False
    prefill_start: Optional[float] = None       # its prefill group began
    req: object = None                          # the engine's Request


@dataclass
class Step:
    """One decode step of the window: when its span ended and how long it
    lasted (the engine's ``("decoder", "decode")`` span), the cohort rows
    that decoded, the cohort bucket the step was padded to, and the KV
    positions those rows attended (their context lengths, summed)."""

    t: float
    rows: int
    bucket: int
    context: int
    dt: float = 0.0


@dataclass
class Span:
    """An engine probe span (``telemetry.probes.Sample``) on the harness
    clock: it ended at ``t`` and lasted ``dt``."""

    phase: str
    brick: str
    t: float
    dt: float
    tokens: int


@dataclass
class RunRecord:
    cell: str
    sizes: dict                      # configuration's ``config`` block
    peaks: dict
    setup_s: float
    w0: float                        # window start
    w1: float                        # window end
    requests: List[Tracked]
    steps: List[Step]
    spans: List[Span]                # engine probe spans inside the window
    tabm_writes: int = 0             # TABM ring writes inside the window
    kv_read_positions: int = 0       # positions one decode row reads
    trace: Optional[dict] = None     # reduced device trace (--trace 1)

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.w0 <= t <= self.w1

    def due_in_window(self) -> List[Tracked]:
        return [r for r in self.requests if self.in_window(r.due)]

    def ttft_s(self) -> List[float]:
        """Time to first token of every request due in the window; one
        with no first token by the window's end enters at its wait so far
        (censored), so a stall cannot hide."""
        out = []
        for r in self.due_in_window():
            first = r.stamps[0] if r.stamps else None
            out.append((first if first is not None and first <= self.w1
                        else self.w1) - r.due)
        return out

    def itl_s(self) -> List[float]:
        """Every gap between consecutive tokens of a request that ends
        inside the window, and for a request still streaming at the
        window's end, the gap open since its last token (censored)."""
        out = []
        for r in self.requests:
            s = [t for t in r.stamps if t <= self.w1]
            for a, b in zip(s, s[1:]):
                if b >= self.w0:
                    out.append(b - a)
            if s and r.done is None and not r.failed and s[-1] >= self.w0:
                out.append(self.w1 - s[-1])
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.requests for t in r.stamps
                   if self.in_window(t))

    def spans_of(self, phase: str) -> List[Span]:
        return [s for s in self.spans if s.phase == phase]


def pct(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), None when empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
