"""Useful FLOP/s of the traced window's linalg calls over the chips'
published bf16 peak, in percent (host clock; each call ends in
``block_until_ready``).  Float32 products at Precision.HIGHEST take six
bf16 passes, which this share does not fold in."""


def read(r):
    calls = [c for c in r.layer.get("calls", []) if c["traced"]]
    if not calls:
        return None
    rate = sum(c["flops"] for c in calls) / sum(c["wall_s"] for c in calls)
    return 100.0 * rate / (r.chips * r.peaks["bf16_flops"])
