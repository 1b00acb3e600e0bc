"""Mean over the traced ``Scheduler.step`` calls (host spans
``bench.step``) of the step's wall time less the device's busy time inside
it, in milliseconds: the scheduler's and backend's host work per step."""


def read(r):
    tl = r.timeline
    steps = [] if tl is None else tl.spans_named("bench.step")
    if not steps:
        return None
    host = [sum((s.dur - d.busy_s(s.start, s.end)) for d in tl.devices)
            / len(tl.devices) for s in steps]
    return 1e3 * sum(host) / len(host)
