"""Set-up time: process start to the window's start (imports, weights,
engine, warm-up compiles or cache loads, backlog fill)."""


def read(run):
    return run.setup_s
