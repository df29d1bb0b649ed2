"""Host time per cohort decode step spent sampling every row after the
first, once the device step is done: the mean of the engine's
``serve.decode.sample`` part (phase ``decode.sample``) of its
``("decoder", "decode")`` span.  A program that does not split the decode
span reads nothing."""


def read(run):
    spans = run.spans_of("decode.sample")
    if not spans:
        return None
    return 1e3 * sum(s.dt for s in spans) / len(spans)
