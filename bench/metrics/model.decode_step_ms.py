"""Device time of the batched decode program per decode step, in
milliseconds: the mean duration of the ``jit_step`` programs (the
backend's vmapped decode step) in the trace."""

from bench.trace import op_base


def read(r):
    tl = r.timeline
    if tl is None:
        return None
    runs = [m.dur for d in tl.devices for m in d.modules
            if op_base(m.name) == "jit_step"]
    return 1e3 * sum(runs) / len(runs) if runs else None
