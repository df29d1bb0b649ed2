"""Device time of one cohort decode step: per decode step inside the
traced window, the union of the device operations that ran within the
step's span, averaged over those steps.  The rest of ``decode_step_ms``
is host time (dispatch and the per-row sampling reads)."""


def read(run):
    tr = run.trace
    if not tr or not tr["steps"] or tr["busy_s"] <= 0:
        return None
    return 1e3 * sum(s["busy_s"] for s in tr["steps"]) / len(tr["steps"])
