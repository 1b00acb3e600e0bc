"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three files: the pallas_call + BlockSpec kernel, ops.py
(jit'd public wrapper: the Pallas interpreter off the TPU, the compiled
kernel on it), and ref.py (pure-jnp oracle used by the allclose test
sweeps).
"""

from .common import TilePlan, heuristic_plan, pad_axes, round_up
from .matmul import matmul, matmul_pallas, matmul_ref
from .trsm import trsm, trsm_diag_pallas, trsm_ref
from .cholesky import cholesky, cholesky_block_pallas, cholesky_ref
from .flash_attention import (flash_attention, flash_attention_pallas,
                              flash_attention_ref)
from .ssm_scan import ssm_scan, ssm_scan_pallas, ssm_scan_ref
