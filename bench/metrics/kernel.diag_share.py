"""Device time of the diagonal-block Pallas kernels (the TRSM and
Cholesky block solves, traced today as ``trsm.<n>`` and
``cholesky.<n>``) over device busy time, in percent."""

from bench.trace import is_pallas, op_base


def read(r):
    tl = r.timeline
    if tl is None:
        return None
    diag = sum(d.op_time(lambda o: is_pallas(o) and op_base(o.name) in
                         ("trsm", "cholesky")) for d in tl.devices)
    busy = sum(d.busy_s() for d in tl.devices)
    return 100.0 * diag / busy if diag > 0 and busy > 0 else None
