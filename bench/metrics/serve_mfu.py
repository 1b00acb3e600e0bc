"""Model FLOP of the serving steps in the traced window over their summed
wall time (host clock, ``Scheduler.step``) and the chips' published bf16
peak, in percent.  Model FLOP: 2 per multiplying weight per token
processed, plus causal attention at each token's context length."""


def read(r):
    steps = [s for s in r.layer.get("steps", []) if s["traced"]]
    if not steps:
        return None
    rate = sum(s["flops"] for s in steps) / sum(s["wall_s"] for s in steps)
    return 100.0 * rate / (r.chips * r.peaks["bf16_flops"])
