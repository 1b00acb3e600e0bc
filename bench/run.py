"""Run one benchmark cell once on the accelerator of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the root of the checkout.
The run exits non-zero, and prints no result, when JAX finds no TPU or
fewer chips than the cell asks for.  Its last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last);
the last lines of standard error give each number compared beside its
limit.
"""

import os
import sys
import time

T0 = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.harness import run_cell
    sys.exit(run_cell(t0=T0))
