from .ops import diag_split, trsm
from .ref import trsm_ref
from .trsm import trsm_diag_inv_pallas, trsm_diag_pallas
