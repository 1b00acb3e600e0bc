"""Distributed Cholesky factorization  A = L L^T  (right-looking, blocked).

Executable counterpart of the §V-style models (the paper models Cholesky
with the same methodology; only Cannon/TRSM equations are printed).

2D: per block-column j on a ("row","col") grid:
  1. factor the diagonal block (owner of (j,j); select-and-reduce bcast),
  2. panel solve on column-j owners:  L_ij = A_ij L_jj^{-T},
  3. broadcast the panel along rows; broadcast the *transposed* panel along
     columns (a single joint-axis ppermute moves block (k,j) -> (j,k)),
  4. trailing update  A_ik -= L_ij L_kj^T  for i,k > j.

2.5D: A replicated over c layers; the trailing update is column-striped
across layers (layer l owns trailing columns with col % c == l) into a
layer-local accumulator; the pivot column is combined with a psum over the
layer axis right before it is factored (the model's ``layer_reduce`` term).
Panel work is replicated across layers — communication, not flops, is what
2.5D saves.

Overlap variants omit the serialization barrier between panel broadcasts
and the trailing update so XLA may overlap them (paper: Pthread comm
thread; TPU: async collectives).
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
#: local diagonal factor hook: A_jj -> L_jj (lower Cholesky factor)
Chol = Callable[[jax.Array], jax.Array]
#: local panel solve hook: (A, L_jj) -> A L_jj^{-T}
PanelSolve = Callable[[jax.Array, jax.Array], jax.Array]


def _default_mm(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST)


def _default_chol(a):
    return jnp.linalg.cholesky(a)


def _default_panel_solve(a, ljj):
    """A L_jj^{-T}: solve X L_jj^T = A (L_jj^T upper-triangular)."""
    return jax.scipy.linalg.solve_triangular(ljj, a.T, lower=True).T


def _bcast_from(x, axis: str, k):
    idx = lax.axis_index(axis)
    return lax.psum(jnp.where(idx == k, x, jnp.zeros_like(x)), axis)


def _transpose_perm(g: int, layers: int = 1):
    perm = []
    for l in range(layers):
        for i in range(g):
            for j in range(g):
                perm.append(((l * g + i) * g + j, (l * g + j) * g + i))
    return perm


def _chol_body(a, *, g: int, layers: int, local_mm: MatMul, local_chol: Chol,
               local_solve: PanelSolve, overlap: bool):
    row = lax.axis_index("row")
    col = lax.axis_index("col")
    lyr = lax.axis_index("lyr") if layers > 1 else 0
    grid_axes = ("lyr", "row", "col") if layers > 1 else ("row", "col")
    tperm = _transpose_perm(g, layers)

    def step(carry, j):
        a_cur, acc, l_acc = carry
        if layers > 1:
            # combine the pivot column's partial updates across layers
            pivot_fix = lax.psum(jnp.where(col == j, acc, jnp.zeros_like(acc)), "lyr")
            a_eff = a_cur - jnp.where(col == j, pivot_fix, jnp.zeros_like(acc))
        else:
            a_eff = a_cur - acc
        # 1. diagonal factor
        ajj = _bcast_from(_bcast_from(a_eff, "row", j), "col", j)
        ljj = local_chol(ajj)
        # 2. panel solve: L_ij = A_ij L_jj^{-T}
        panel = local_solve(a_eff, ljj)
        lj = jnp.where((col == j) & (row > j), panel, jnp.zeros_like(panel))
        lj = lj + jnp.where((col == j) & (row == j), ljj, jnp.zeros_like(ljj))
        # 3. panel along rows; transposed panel along columns
        lj_row = lax.psum(lj, "col")
        ljT = lax.ppermute(lj, grid_axes, tperm)
        lkj = lax.psum(jnp.where(row == j, ljT, jnp.zeros_like(ljT)), "row")
        if not overlap:
            (a_cur, acc, lj_row, lkj) = lax.optimization_barrier(
                (a_cur, acc, lj_row, lkj))
        # 4. trailing update
        upd = local_mm(lj_row, lkj.swapaxes(-1, -2))
        trailing = (row > j) & (col > j)
        if layers > 1:
            mine = (col % layers) == lyr
            acc = acc + jnp.where(trailing & mine, upd, jnp.zeros_like(upd))
        else:
            acc = acc + jnp.where(trailing, upd, jnp.zeros_like(upd))
        l_acc = jnp.where(col == j, lj_row, l_acc)
        # keep only the lower triangle of the (j,j) block
        return (a_cur, acc, l_acc), None

    zeros = jnp.zeros_like(a)
    carry0 = (a, zeros, zeros)
    if layers > 1:
        # the body's layer-striped masks make the carry vary over 'lyr'
        carry0 = jax.tree.map(
            lambda x: lax.pcast(x, ("lyr",), to="varying"), carry0)
    (a, acc, l_acc), _ = lax.scan(step, carry0, jnp.arange(g))
    if layers > 1:
        # All layers computed identical panels; select layer 0's copy via a
        # reduction over the layer axis — the model's gather_L term.
        l_acc = lax.psum(
            jnp.where(lyr == 0, l_acc, jnp.zeros_like(l_acc)), "lyr")
    # mask strictly-upper blocks and the upper triangle of diagonal blocks
    bs = l_acc.shape[0]
    tri = jnp.tril(jnp.ones((bs, bs), l_acc.dtype))
    l_acc = jnp.where(row == col, l_acc * tri, l_acc)
    l_acc = jnp.where(row < col, jnp.zeros_like(l_acc), l_acc)
    return l_acc


def _make(mesh, *, overlap: bool, local_mm: Optional[MatMul] = None,
          local_chol: Optional[Chol] = None,
          local_solve: Optional[PanelSolve] = None, check_vma: bool = True):
    g = grid_size(mesh)
    layers = n_layers(mesh)
    fn = functools.partial(_chol_body, g=g, layers=layers,
                           local_mm=local_mm or _default_mm,
                           local_chol=local_chol or _default_chol,
                           local_solve=local_solve or _default_panel_solve,
                           overlap=overlap)
    spec = P("row", "col")  # replicated over lyr when present
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=check_vma))


def make(mesh, variant: str, *, local_mm: Optional[MatMul] = None,
         local_chol: Optional[Chol] = None,
         local_solve: Optional[PanelSolve] = None, check_vma: bool = True):
    """Reusable compiled executor: A -> L for the given variant (the
    2d/2.5d split is carried by the mesh's layer axis).  ``check_vma=False``
    for locals the varying-axis checker cannot type (Pallas kernels)."""
    return _make(mesh, overlap=variant.endswith("ovlp"), local_mm=local_mm,
                 local_chol=local_chol, local_solve=local_solve,
                 check_vma=check_vma)


def cholesky_2d(A, *, mesh, local_mm: Optional[MatMul] = None,
                local_chol: Optional[Chol] = None,
                local_solve: Optional[PanelSolve] = None):
    """L with A = L L^T; A block-distributed on ("row","col")."""
    return make(mesh, "2d", local_mm=local_mm, local_chol=local_chol,
                local_solve=local_solve)(A)


def cholesky_2d_ovlp(A, *, mesh, local_mm: Optional[MatMul] = None,
                     local_chol: Optional[Chol] = None,
                     local_solve: Optional[PanelSolve] = None):
    return make(mesh, "2d_ovlp", local_mm=local_mm, local_chol=local_chol,
                local_solve=local_solve)(A)


def cholesky_25d(A, *, mesh, local_mm: Optional[MatMul] = None,
                 local_chol: Optional[Chol] = None,
                 local_solve: Optional[PanelSolve] = None):
    """2.5D on a ("lyr","row","col") mesh; A replicated over layers."""
    return make(mesh, "2.5d", local_mm=local_mm, local_chol=local_chol,
                local_solve=local_solve)(A)


def cholesky_25d_ovlp(A, *, mesh, local_mm: Optional[MatMul] = None,
                      local_chol: Optional[Chol] = None,
                      local_solve: Optional[PanelSolve] = None):
    return make(mesh, "2.5d_ovlp", local_mm=local_mm, local_chol=local_chol,
                local_solve=local_solve)(A)
