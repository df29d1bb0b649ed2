"""Mean wall time of one cohort decode step (the engine's
``("decoder", "decode")`` span, per-row sampling reads included)."""


def read(run):
    if not run.steps:
        return None
    return 1e3 * sum(s.dt for s in run.steps) / len(run.steps)
