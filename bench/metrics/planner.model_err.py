"""How far the planner's predicted call time is from the measured one:
the geometric mean, over the traced window's calls, of
|ln(plan.predicted["total"] / measured call wall)|."""

import math


def read(r):
    errs = [abs(math.log(c["predicted_s"] / c["wall_s"]))
            for c in r.layer.get("calls", []) if c["traced"]]
    if not errs:
        return None
    return math.exp(sum(math.log(max(e, 1e-12)) for e in errs) / len(errs))
