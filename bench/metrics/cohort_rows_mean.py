"""Mean cohort rows that decoded per step in the window."""


def read(run):
    if not run.steps:
        return None
    return sum(s.rows for s in run.steps) / len(run.steps)
