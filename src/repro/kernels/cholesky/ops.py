"""Blocked Cholesky (right-looking) composed from all three linalg kernels:
diagonal factor (cholesky kernel), panel solve (trsm kernel: L_ij L_jj^T =
A_ij), trailing syrk update (matmul kernel).  A is identity-extended to
the block, so every shape runs through the kernels.  Each panel solve
is the trsm wrapper on one diagonal block, L_jj^T: panels with many rows
against the block invert L_jj^T once and apply it on the MXU, the last,
short panels back-substitute (``trsm.ops.diag_split``)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import (MIN_TILE, TilePlan, pad_eye, resolve_interpret,
                      round_up, tile_block)
from ..matmul.ops import matmul
from ..trsm.ops import diag_split as trsm_diag_split, trsm
from .cholesky import cholesky_block_pallas


def diag_split(n: int, block: int = 256) -> Tuple[int, int]:
    """(inverted, substituted) over the panel solves of ``cholesky`` on A
    (n, n) at ``block``: ``trsm.ops.diag_split`` of each panel's rows."""
    block = min(block, round_up(n, MIN_TILE))
    n = round_up(n, block)
    splits = [trsm_diag_split(n - jj, block, block)
              for jj in range(block, n, block)]
    return sum(s[0] for s in splits), sum(s[1] for s in splits)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block", "tiles",
                                    "mm_tiles"))
def cholesky(a: jax.Array, *, block: int = 256,
             interpret: Optional[bool] = None,
             tiles: Optional[TilePlan] = None,
             mm_tiles: Optional[TilePlan] = None) -> jax.Array:
    """L with L L^T = A (A SPD, (n, n)).

    ``tiles`` (a cholesky :class:`TilePlan`, dim ``block``) overrides the
    panel width (the panel trsm necessarily solves at that width);
    ``mm_tiles`` is threaded to the dgemm-shaped trailing updates.
    ``interpret`` defaults to the platform (see ``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    block = tile_block(tiles, "cholesky", "block", block)
    n0 = a.shape[0]
    block = min(block, round_up(n0, MIN_TILE))
    a = pad_eye(a, round_up(n0, block))
    n = a.shape[0]
    nb = n // block
    acc = a
    l_cols = []
    for j in range(nb):
        jj = j * block
        ajj = jax.lax.slice(acc, (jj, jj), (jj + block, jj + block))
        ljj = cholesky_block_pallas(ajj, interpret=interpret)
        if j + 1 < nb:
            # panel: L_ij = A_ij (L_jj^T)^{-1}  =>  X U = B with U = L_jj^T
            a_panel = jax.lax.slice(acc, (jj + block, jj), (n, jj + block))
            l_panel = trsm(ljj.T, a_panel, block=block, interpret=interpret,
                           mm_tiles=mm_tiles)
            # trailing syrk: A_trail -= L_panel @ L_panel^T
            upd = matmul(l_panel, l_panel.T, interpret=interpret,
                         out_dtype=acc.dtype, tiles=mm_tiles)
            trail = jax.lax.slice(acc, (jj + block, jj + block), (n, n)) - upd
            acc = jax.lax.dynamic_update_slice(acc, trail,
                                               (jj + block, jj + block))
            col = jnp.concatenate([ljj, l_panel], axis=0)
        else:
            col = ljj
        col_full = jnp.pad(col, ((jj, 0), (0, 0)))
        l_cols.append(col_full)
    return jnp.concatenate(l_cols, axis=1)[:n0, :n0]
