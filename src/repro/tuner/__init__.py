"""repro.tuner — model-guided autotuning dispatch.

The paper's headline application, closed end-to-end: the analytic
performance models (cost-IR programs in ``repro.perf`` over the machine
surfaces of ``repro.core``) select the 2D/2.5D ±overlap variant,
replication factor and grid for a scenario, and the executable
``shard_map`` algorithms (``repro.linalg``) run the winner with the Pallas
kernels (``repro.kernels``) as local compute.

Layout:
  registry.py   PerfModelRegistry — one query surface over the algorithm
                models, collective models, and machine surfaces
  select.py     candidate scoring (one vectorized evaluation per model) +
                the paper's §VI variant selection and prediction tables
  paper_fit.py  fits the Hopper surfaces to the paper's published tables
  plan.py       ExecutionPlan + persistent JSON PlanCache (artifacts/plans/)
  autotune.py   Tuner — feasible-grid enumeration + model selection +
                LM-layer consultations (fsdp layout, prefill chunking)
  dispatch.py   linalg.matmul/trsm/cholesky execution of resolved plans
"""

from .registry import (DEFAULT_REGISTRY, MachineSurface, PerfModelRegistry,
                       build_default_registry, machine_for_devices,
                       machine_for_platform)
from .plan import (ExecutionPlan, PlanCache, default_plan_dir,
                   machine_fingerprint, plan_key)
from .autotune import OP_ALGOS, Tuner, default_tuner, feasible_grids

__all__ = [
    "DEFAULT_REGISTRY", "MachineSurface", "PerfModelRegistry",
    "build_default_registry", "machine_for_devices", "machine_for_platform",
    "ExecutionPlan", "PlanCache", "default_plan_dir", "machine_fingerprint",
    "plan_key",
    "OP_ALGOS", "Tuner", "default_tuner", "feasible_grids",
]
