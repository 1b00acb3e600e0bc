"""Readings of a cell's numbers for the program and for its control, on
several seeds in one process, on the cell's own devices and sizes.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 15]

The control is the configuration's plain reference put in the program's
place one precision below the configuration's (see each reference).  For
every seed the run prints one JSON line: each number the cell compares,
read for the program and for the control.  A limit lies between the
largest program reading and the smallest control reading.  The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(argv=None, *, root=ROOT, require_tpu=True, out=None):
    from bench import harness

    out = out or sys.stdout
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0,
                    help="window of each seed, where the cell has one")
    a = ap.parse_args(argv)
    args = argparse.Namespace(workload=a.workload, seed=a.seeds[0],
                              seconds=a.seconds, trace=0)
    work_dir = tempfile.mkdtemp(prefix="bench-control-")
    try:
        cell = harness.open_cell(args, work_dir, root=root,
                                 require_tpu=require_tpu)
        job = cell.kind.make(cell.ctx)
        job.setup()
        rows = []
        for seed in a.seeds:
            row = {"seed": seed, **job.control(seed, a.seconds)}
            print(json.dumps(row), file=out, flush=True)
            rows.append(row)
        return rows
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    readings()
