"""Device time of the prefill programs (``jit_serve_step``: the 128-token
chunks and the one-token tail) per 1000 prompt tokens prefilled in the
traced window, in milliseconds."""

from bench.trace import op_base


def read(r):
    tl = r.timeline
    tokens = sum(s["prefill_tokens"] for s in r.layer.get("steps", [])
                 if s["traced"])
    if tl is None or not tokens:
        return None
    dev = sum(m.dur for d in tl.devices for m in d.modules
              if op_base(m.name) == "jit_serve_step") / len(tl.devices)
    return 1e6 * dev / tokens if dev > 0 else None
