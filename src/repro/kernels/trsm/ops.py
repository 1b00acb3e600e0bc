"""Blocked triangular solve built from the diagonal-block kernel + the MXU
matmul kernel: all O(n^3) off-diagonal work is dgemm-shaped.  Operands
are padded (U identity-extended, B zero-extended) to the block, so every
shape runs through the kernels.

The blocking is recursive halving (LAPACK's recursive TRSM): split the
diagonal blocks in two, solve the left half, fold it into the right half
with one dgemm of contraction width h, solve the right half.  The dgemms
do the same FLOP as a right-looking loop over the blocks, but B is
rewritten elementwise once per level, O(log nb) times, instead of once
per block."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import (MIN_TILE, TilePlan, pad_axes, pad_eye,
                      resolve_interpret, round_up, tile_block)
from ..matmul.ops import matmul
from .trsm import trsm_diag_pallas


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block", "tiles",
                                    "mm_tiles"))
def trsm(u: jax.Array, b: jax.Array, *, block: int = 256,
         interpret: Optional[bool] = None, tiles: Optional[TilePlan] = None,
         mm_tiles: Optional[TilePlan] = None) -> jax.Array:
    """Solve X U = B; U (n, n) upper-triangular, B (m, n).

    ``tiles`` (a trsm :class:`TilePlan`, dim ``block``) overrides the block
    size, the width of the diagonal solves; ``mm_tiles`` is threaded to
    the update dgemms.  A single block is one diagonal kernel.
    ``interpret`` defaults to the platform (see ``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    block = tile_block(tiles, "trsm", "block", block)
    n0 = u.shape[0]
    m0 = b.shape[0]
    block = min(block, round_up(n0, MIN_TILE))
    u = pad_eye(u, round_up(n0, block))
    b = pad_axes(b, {0: MIN_TILE, 1: block})

    def solve(u, b):
        nb = u.shape[0] // block
        if nb == 1:
            return trsm_diag_pallas(u, b, interpret=interpret)
        h = (nb // 2) * block
        x1 = solve(u[:h, :h], b[:, :h])
        # B_2 -= X_1 U_12: one dgemm with contraction width h
        b2 = b[:, h:] - matmul(x1, u[:h, h:], interpret=interpret,
                               out_dtype=b.dtype, tiles=mm_tiles)
        return jnp.concatenate([x1, solve(u[h:, h:], b2)], axis=1)

    return solve(u, b)[:m0, :n0]
