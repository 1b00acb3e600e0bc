"""repro.perf — the composable cost-IR behind every performance model.

The paper's methodology (§IV-V) builds every algorithm model from three
ingredients — local-routine times ``T_rout``, calibrated transfers
``T_comm`` / ``T_comm_sync``, and analytic collective schedules — combined
by sequencing, loops, and max-overlap.  This package makes that composition
first-class:

  expr.py      symbolic scenario parameters (n, p, c, r, q, d) and the
               closed-form sums that collapse triangular loops
  ir.py        the node set: Compute, P2P, SyncP2P, Collective, Seq,
               Loop, Overlap — and Program, a registered model
  evaluate.py  the evaluator: calibration applied in exactly one place,
               est_Cal / est_NoCal / est_ideal chosen by EvalOptions,
               vectorized over numpy grids of scenarios
  models.py    the paper's 16 variants + LU 2D/2.5D as IR programs, the
               ALGOS x VARIANTS matrix and each algorithm's useful flops
  kernel.py    the intra-kernel tier: tile plans for the local Pallas
               kernels

Only ``repro.core`` sits below this package.  Selection over the programs
(``repro.tuner.select``, ``repro.tuner.Tuner``) evaluates whole scenario
grids in one pass via ``evaluate_program``; ``ModelResult`` is the
scalar view of one scenario.
"""

from .expr import (C, D, Expr, N, P, Param, Q, R, T, as_expr, floor, fmax,
                   fmin, rint, sqrt, sum_decreasing, sum_squares, where)
from .ir import (COLLECTIVE_KINDS, Collective, Compute, Loop, Node, Overlap,
                 P2P, Program, Seq, SyncP2P)
from .evaluate import (EVAL_MODES, CollectiveStep, EvalOptions, EvalResult,
                       MODEL_VERSION, ModelResult, PhaseCost,
                       collective_schedule, evaluate_program, pct_of_peak,
                       result_from_eval)
from .models import (ALGOS, PROGRAMS, USEFUL_FLOPS, VARIANTS, build_programs,
                     lu_2d, lu_25d)
from .kernel import (ALGO_KERNELS, CANDIDATE_SIZES, KERNEL_DIMS, KernelModel,
                     KernelPhases, KernelWork, MIN_TILE, TilePlan,
                     VMEM_BUDGET, candidate_tiles, heuristic_matmul_blocks,
                     heuristic_plan, itemsize_of, kernel_work, tiles_for_plan)

__all__ = [
    "C", "D", "Expr", "N", "P", "Param", "Q", "R", "T", "as_expr", "floor",
    "fmax", "fmin", "rint", "sqrt", "sum_decreasing", "sum_squares", "where",
    "COLLECTIVE_KINDS", "Collective", "Compute", "Loop", "Node", "Overlap",
    "P2P", "Program", "Seq", "SyncP2P",
    "EVAL_MODES", "CollectiveStep", "EvalOptions", "EvalResult",
    "MODEL_VERSION", "ModelResult", "PhaseCost", "collective_schedule",
    "evaluate_program", "pct_of_peak", "result_from_eval",
    "ALGOS", "PROGRAMS", "USEFUL_FLOPS", "VARIANTS", "build_programs",
    "lu_2d", "lu_25d",
    "ALGO_KERNELS", "CANDIDATE_SIZES", "KERNEL_DIMS", "KernelModel",
    "KernelPhases", "KernelWork", "MIN_TILE", "TilePlan", "VMEM_BUDGET",
    "candidate_tiles", "heuristic_matmul_blocks", "heuristic_plan",
    "itemsize_of", "kernel_work", "tiles_for_plan",
]
