"""Mean wall time, in seconds, from ``Scheduler.submit`` to the start of
a request's first prefill: the ``queued_s`` arg of the first
``repro.serve.prefill`` span of each request, over those spans in the
traced window.  Under FIFO it is the wait behind other requests'
prefills and the decode steps between them; the rest of the time to
first token is the request's own prefill."""

from bench import program_spans


def read(r):
    if r.timeline is None:
        return None
    waits = [s.args["queued_s"] for s in program_spans.named(
        program_spans.for_timeline(r.timeline), "repro.serve.prefill")
        if "queued_s" in s.args]
    return sum(waits) / len(waits) if waits else None
