"""The offered-load sweep of a serving cell: one window at each rate, in
one process, on the cell's own devices, weights and mix.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 0.5 0.7 0.9 \\
        [--seconds 50]

For every rate the run prints the kind's summary and one JSON line: the
time-to-first-token percentiles, those of the window's first and last
thirds of requests (a backlog that grows shows as a last third far above
the first), the drain after the window, and the output tokens completed
per second of the window.  The highest rate whose backlog does not grow
is the knee; a cell's mix offers a fixed share of it.  The benchmark's
own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def sweep(argv=None, *, root=ROOT, require_tpu=True, out=None):
    from bench import harness, work

    out = out or sys.stdout
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    a = ap.parse_args(argv)
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=0)
    work_dir = tempfile.mkdtemp(prefix="bench-sweep-")
    rows = []
    try:
        cell = harness.open_cell(args, work_dir, root=root,
                                 require_tpu=require_tpu)
        job = cell.kind.make(cell.ctx)
        job.setup()
        for rate in a.rates:
            job.t = dict(job.t, rate_per_s=rate)
            job.steps = []
            job.window(a.seconds)
            job.finish()
            print(job.summary(), file=out, flush=True)
            ttft = job._ttfts()
            by_due = [t for _, t in sorted(zip(
                (s.due for s in job.streams), ttft))]
            third = max(1, len(by_due) // 3)
            row = {"rate": rate, "n": len(ttft),
                   "failed": sum(len(s.tokens) != s.n_out
                                 for s in job.streams),
                   **{f"ttft_p{q}_s": work.percentile(ttft, q)
                      for q in (50, 75, 90)},
                   "ttft_first_third_s": sum(by_due[:third]) / third,
                   "ttft_last_third_s": sum(by_due[-third:]) / third,
                   "drain_s": job.t_done - job.t_end,
                   "out_tokens_per_s": sum(
                       sum(t <= job.t_end for t in s.times)
                       for s in job.streams) / a.seconds,
                   **job.end_to_end()}
            print(json.dumps(row), file=out, flush=True)
            rows.append(row)
            job.release()
        return rows
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    sweep()
