"""Requests staged per TABM commit in the window: ring writes over
staging microbatches (one ``stage`` span of the producer brick each)."""
from collections import Counter


def read(run):
    per_brick = Counter(s.brick for s in run.spans_of("stage"))
    commits = max(per_brick.values()) if per_brick else 0
    if not commits:
        return None
    return run.tabm_writes / commits
