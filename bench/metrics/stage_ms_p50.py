"""TABM staging time, median: from the engine's submit of a request due
in the window to the commit of its staged slab (``Request.staged_t -
Request.submit_t``, both stamped by the engine on ``time.monotonic``).
A program without those stamps reads nothing."""
from bench.record import pct


def read(run):
    waits = []
    for r in run.due_in_window():
        a = getattr(r.req, "submit_t", None)
        b = getattr(r.req, "staged_t", None)
        if a is not None and b is not None:
            waits.append(b - a)
    v = pct(waits, 50)
    return None if v is None else 1e3 * v
