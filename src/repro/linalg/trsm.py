"""Distributed triangular solve  X * U = B  (U upper-triangular).

Executable counterpart of the paper's §V-B models.

2D (``trsm_2d``): right-looking over block columns on a ("row","col") grid.
Per block-column j:
  1. broadcast U_jj (select-and-reduce over both axes — the model's
     ``T_bcast_sync`` along columns),
  2. local dtrsm on the owners of X's column j,
  3. broadcast the solved X_:j along grid rows (``T_bcast`` distance 1),
  4. broadcast U_j,: along grid columns and update the trailing matrix.

2.5D (``trsm_25d``): the paper replicates U across c layers and *scatters
the rows of X* among them — rows of X are independent, so each layer runs
the 2D algorithm on its row slice with its own ("row","col") sub-grid; the
final gather is expressed by the output sharding over the flattened
("lyr","row") axis.  This is exactly the executable shape of the paper's
model (scatter_X + per-layer loop + gather_X).

Overlap variants prefetch the *next* U panel during the trailing update
(the paper's Pthread-dedicated-to-comm trick; here: no data dependency =>
XLA may overlap).

The executable versions use r=1 block-cyclic factor (one block per process
per dimension); the performance models support general r — see DESIGN.md.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .grid import grid_size, n_layers

MatMul = Callable[[jax.Array, jax.Array], jax.Array]
#: local solve hook: (B, U) -> X with X U = B (U upper-triangular); the
#: Pallas trsm kernel plugs in here via the tuner dispatch layer.
SolveXU = Callable[[jax.Array, jax.Array], jax.Array]


def _default_mm(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST)


def _solve_xu(b, u):
    """Local X U = B  =>  X = B U^{-1} (U upper)."""
    # solve_triangular solves a x = b; for x u = b use transpose:
    # (u^T x^T = b^T) with u^T lower.
    return jax.scipy.linalg.solve_triangular(
        u.T, b.T, lower=True).T


def _bcast_from(x, axis: str, k):
    """Select-and-reduce broadcast of the axis-index-k owner's block."""
    idx = lax.axis_index(axis)
    return lax.psum(jnp.where(idx == k, x, jnp.zeros_like(x)), axis)


def _trsm_body(u, b, *, g: int, local_mm: MatMul, local_solve: SolveXU,
               overlap: bool):
    row = lax.axis_index("row")
    col = lax.axis_index("col")

    def diag_u(j):
        # U_jj to everyone: broadcast along rows then columns
        return _bcast_from(_bcast_from(u, "row", j), "col", j)

    def u_panel(j):
        # U_j,: (block row j) to all rows
        return _bcast_from(u, "row", j)

    def step(carry, j):
        b_cur, x_acc, ujj, upan = carry
        # 2. local solve for the owners of column j
        xj = local_solve(b_cur, ujj)
        xj = jnp.where(col == j, xj, jnp.zeros_like(xj))
        # 3. broadcast X_:j along rows
        xj_b = lax.psum(xj, "col")
        if overlap:
            # prefetch next iteration's U blocks during the update
            ujj_nxt = diag_u(jnp.minimum(j + 1, g - 1))
            upan_nxt = u_panel(jnp.minimum(j + 1, g - 1))
        else:
            (b_cur, x_acc, xj_b) = lax.optimization_barrier((b_cur, x_acc, xj_b))
            ujj_nxt, upan_nxt = ujj, upan
        # 4. trailing update: B_:k -= X_:j @ U_jk for k > j
        upd = local_mm(xj_b, upan)
        b_new = jnp.where(col > j, b_cur - upd, b_cur)
        x_acc = jnp.where(col == j, xj_b, x_acc)
        if not overlap:
            ujj_nxt = diag_u(jnp.minimum(j + 1, g - 1))
            upan_nxt = u_panel(jnp.minimum(j + 1, g - 1))
        return (b_new, x_acc, ujj_nxt, upan_nxt), None

    x0 = jnp.zeros_like(b)
    carry = (b, x0, diag_u(0), u_panel(0))
    (b, x, _, _), _ = lax.scan(step, carry, jnp.arange(g))
    return x


def _make_2d(mesh, *, overlap: bool, local_mm: Optional[MatMul] = None,
             local_solve: Optional[SolveXU] = None, check_vma: bool = True):
    g = grid_size(mesh)
    layers = n_layers(mesh)
    fn = functools.partial(_trsm_body, g=g, local_mm=local_mm or _default_mm,
                           local_solve=local_solve or _solve_xu,
                           overlap=overlap)
    if layers > 1:
        # 2.5D: U replicated over layers; B/X rows scattered over (lyr,row).
        u_spec = P("row", "col")
        bx_spec = P(("lyr", "row"), "col")
    else:
        u_spec = P("row", "col")
        bx_spec = P("row", "col")
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(u_spec, bx_spec),
                                 out_specs=bx_spec, check_vma=check_vma))


def make(mesh, variant: str, *, local_mm: Optional[MatMul] = None,
         local_solve: Optional[SolveXU] = None, check_vma: bool = True):
    """Reusable compiled executor: (U, B) -> X for the given variant (the
    2d/2.5d split is carried by the mesh's layer axis).  ``check_vma=False``
    for locals the varying-axis checker cannot type (Pallas kernels)."""
    return _make_2d(mesh, overlap=variant.endswith("ovlp"),
                    local_mm=local_mm, local_solve=local_solve,
                    check_vma=check_vma)


def trsm_2d(U, B, *, mesh, local_mm: Optional[MatMul] = None,
            local_solve: Optional[SolveXU] = None):
    """Solve X U = B; U and B block-distributed on ("row","col")."""
    return _make_2d(mesh, overlap=False, local_mm=local_mm,
                    local_solve=local_solve)(U, B)


def trsm_2d_ovlp(U, B, *, mesh, local_mm: Optional[MatMul] = None,
                 local_solve: Optional[SolveXU] = None):
    return _make_2d(mesh, overlap=True, local_mm=local_mm,
                    local_solve=local_solve)(U, B)


def trsm_25d(U, B, *, mesh, local_mm: Optional[MatMul] = None,
             local_solve: Optional[SolveXU] = None):
    """2.5D: mesh ("lyr","row","col"); U replicated per layer, B rows
    scattered across layers."""
    return _make_2d(mesh, overlap=False, local_mm=local_mm,
                    local_solve=local_solve)(U, B)


def trsm_25d_ovlp(U, B, *, mesh, local_mm: Optional[MatMul] = None,
                  local_solve: Optional[SolveXU] = None):
    return _make_2d(mesh, overlap=True, local_mm=local_mm,
                    local_solve=local_solve)(U, B)
