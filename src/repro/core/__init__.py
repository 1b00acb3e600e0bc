"""repro.core — the paper's contribution: contention-calibrated performance
models for distributed dense linear algebra (and, beyond the paper, for LM
train/serve steps on TPU meshes).

Layout:
  machine.py      machine constants (Hopper Cray XE6, TPU v5e, CPU host)
  perfmodel.py    alpha-beta + calibration-factor primitives (paper §IV)
                  and AlgoContext, the surfaces a model evaluation reads
  collectives.py  analytic collective models (paper §V, scalar closed forms)
  calibration.py  portable benchmarks + fitting (paper §IV, Figs. 1-4)
  paper_data.py   the paper's published tables (§VI)
  fitting.py      derivative-free fitting helpers
  roofline.py     3-term TPU roofline from compiled HLO (§Roofline)
  hlo.py          structural HLO parsing (trip-count-corrected costs)
  lm_model.py     the methodology applied to LM steps (beyond-paper)

This is the bottom layer: imports point one way, core -> perf -> tuner.
The algorithm models are cost-IR programs in ``repro.perf``; variant
selection and the prediction tables (§VI) are ``repro.tuner.select``.
``lm_model`` is the one exception: it reads ``repro.sim`` and
``repro.configs``.
"""

from .machine import (CPU_HOST, HOPPER, KernelConstants, MACHINES, TPU_V5E,
                      Machine)
from .perfmodel import (AlgoContext, CalibrationTable, CommModel,
                        ComputeModel, EfficiencyCurve, IdentityCalibration,
                        ParametricCalibration)

__all__ = [
    "CPU_HOST", "HOPPER", "KernelConstants", "MACHINES", "TPU_V5E", "Machine",
    "AlgoContext", "CalibrationTable", "CommModel", "ComputeModel",
    "EfficiencyCurve", "IdentityCalibration", "ParametricCalibration",
]
