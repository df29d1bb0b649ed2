"""Admission + TABM staging wait, 95th percentile: from when a request
was due to when its prefill group began (the start of the engine's
``("decoder", "prefill")`` span that produced its first token)."""
from bench.record import pct


def read(run):
    waits = [r.prefill_start - r.due for r in run.due_in_window()
             if r.prefill_start is not None]
    v = pct(waits, 95)
    return None if v is None else 1e3 * v
