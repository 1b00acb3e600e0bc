"""Host wall time per batched decode step, in milliseconds, outside the
decode program's launch: the ``repro.serve.decode.stack`` (stacking
tokens and caches, dummy rows), ``repro.serve.decode.unstack`` (slicing
each row's cache back out) and ``repro.serve.sample`` (each row's
sampling) spans of the traced window, summed, over the number of
``repro.serve.decode.step`` spans there."""

from bench import program_spans

HOST = ("repro.serve.decode.stack", "repro.serve.decode.unstack",
        "repro.serve.sample")


def read(r):
    if r.timeline is None:
        return None
    spans = program_spans.for_timeline(r.timeline)
    steps = len(program_spans.named(spans, "repro.serve.decode.step"))
    if not steps:
        return None
    return 1e3 * sum(s.dur for s in spans if s.name in HOST) / steps
