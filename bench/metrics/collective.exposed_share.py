"""Collective time during which no other operation runs on the chip, over
the traced window, in percent; the mean over the cell's chips."""

from bench.trace import is_collective


def read(r):
    tl = r.timeline
    if tl is None or not any(is_collective(o) for d in tl.devices
                             for o in d.ops):
        return None
    exposed = sum(d.collective_exposed_s() for d in tl.devices)
    return 100.0 * exposed / len(tl.devices) / tl.window_s
