"""Mean host time of a linalg call, in milliseconds: the duration of the
``repro.linalg.<op>`` spans in the traced window, which cover planning,
padding, distribution and the executor's launch, and end before the
device has finished the call's work."""

from bench import program_spans


def read(r):
    if r.timeline is None:
        return None
    calls = [s.dur for s in program_spans.for_timeline(r.timeline)
             if s.name.startswith("repro.linalg.")]
    return 1e3 * sum(calls) / len(calls) if calls else None
