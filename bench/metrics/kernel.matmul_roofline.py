"""The Pallas matmul kernel's share of its roofline, in percent: the
larger of FLOP / peak FLOP/s and HBM bytes / peak bytes/s, summed over the
kernel's calls in the trace, over their summed device time.  FLOP and
bytes come from each call's shapes and the plan's tiles
(``bench.work.matmul_kernel_work``).  Today the kernel's operations are
named ``matmul.<n>`` in the trace; the tiles are those of the operation
whose program (``jit__<op>_body``) runs the call."""

import bisect

from bench import work
from bench.trace import is_pallas, op_base


def _op_of(module_name: str) -> str:
    if "trsm" in module_name:
        return "trsm"
    if "chol" in module_name:
        return "cholesky"
    return "matmul"


def read(r):
    tl, tiles = r.timeline, r.layer.get("tiles", {})
    if tl is None or not tiles:
        return None
    t_min = t_run = 0.0
    for d in tl.devices:
        mods = sorted(d.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        for o in d.ops:
            if not (is_pallas(o) and op_base(o.name) == "matmul"):
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            op = _op_of(mods[i].name) if i >= 0 else "matmul"
            mm = (tiles.get(op) or next(iter(tiles.values()))).get("matmul")
            (dt, (m, n)), ops = work.hlo_shapes(o.name)
            k = ops[0][1][1]
            flops, nbytes = work.matmul_kernel_work(m, k, n, mm,
                                                    work.itemsize(dt))
            t_min += max(flops / r.peaks["bf16_flops"],
                         nbytes / r.peaks["hbm_bytes_s"])
            t_run += o.dur
    return 100.0 * t_min / t_run if t_run > 0 else None
