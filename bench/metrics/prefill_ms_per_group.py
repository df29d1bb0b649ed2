"""Mean wall time of one grouped prefill (the engine's
``("decoder", "prefill")`` span, which ends at host syncs)."""


def read(run):
    spans = run.spans_of("prefill")
    if not spans:
        return None
    return 1e3 * sum(s.dt for s in spans) / len(spans)
