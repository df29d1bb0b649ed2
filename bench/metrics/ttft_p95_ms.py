"""95th percentile time to first token of the requests due in the
window, from when each was due; unfinished ones enter at their wait so
far."""
from bench.record import pct


def read(run):
    v = pct(run.ttft_s(), 95)
    return None if v is None else 1e3 * v
