"""Share of the traced window in which no operation ran on the device,
in percent; the mean over the cell's chips."""


def read(r):
    tl = r.timeline
    return None if tl is None else 100.0 * tl.idle_share()
