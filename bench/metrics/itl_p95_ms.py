"""95th percentile of every gap between consecutive tokens of every
request, in the window (a gap still open at the window's end included)."""
from bench.record import pct


def read(run):
    v = pct(run.itl_s(), 95)
    return None if v is None else 1e3 * v
