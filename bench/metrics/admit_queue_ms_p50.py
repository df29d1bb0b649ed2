"""Admission queueing, median: from the commit of a due request's staged
slab to the start of its prefill group (``Request.admit_t -
Request.staged_t``, both stamped by the engine on ``time.monotonic``): the
wait for the next admission round.  A program without those stamps reads
nothing."""
from bench.record import pct


def read(run):
    waits = []
    for r in run.due_in_window():
        a = getattr(r.req, "staged_t", None)
        b = getattr(r.req, "admit_t", None)
        if a is not None and b is not None:
            waits.append(b - a)
    v = pct(waits, 50)
    return None if v is None else 1e3 * v
