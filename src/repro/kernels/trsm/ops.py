"""Blocked triangular solve built from the diagonal-block kernel + the MXU
matmul kernel: all O(n^3) off-diagonal work is dgemm-shaped.  Operands
are padded (U identity-extended, B zero-extended) to the block, so every
shape runs through the kernels.

The blocking is recursive halving (LAPACK's recursive TRSM): split the
diagonal blocks in two, solve the left half, fold it into the right half
with one dgemm of contraction width h, solve the right half.  The dgemms
do the same FLOP as a right-looking loop over the blocks, but B is
rewritten elementwise once per level, O(log nb) times, instead of once
per block.

The base case, one diagonal block against every row of B, takes one of
two paths by the call's shape (:func:`diag_split`): when B has at least
twice as many rows as the block is wide, every diagonal block is
inverted once, in one batched kernel call on the block's own rows, and
each base case is the matmul B_i U_ii^{-1} on the MXU; otherwise the
diagonal kernel back-substitutes every row of B on the VPU."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import (MIN_TILE, TilePlan, pad_axes, pad_eye,
                      resolve_interpret, round_up, tile_block)
from ..matmul.ops import matmul
from .trsm import trsm_diag_inv_pallas, trsm_diag_pallas


def diag_split(m: int, n: int, block: int = 256) -> Tuple[int, int]:
    """(inverted, substituted): how many of the diagonal blocks of
    ``trsm`` on U (n, n) and B (m, n) at ``block`` are inverted once and
    applied on the MXU, and how many are back-substituted over every row
    of B.  The wrapper decides by this and nothing else.

    Substituting a block of width b costs the VPU kernel m b^2
    multiply-adds; inverting it costs the same kernel on the block's own
    b rows, b^3 (half that, the zero columns skipped), and the apply
    2 m b^2 FLOP on the MXU, which runs float32 at HIGHEST about a
    hundred times faster than the column loop (v5e: the matmul kernel
    ~30 TFLOP/s, the diagonal kernel ~0.3).  So the inverse costs about
    b/m + 1/50 of the substitution: with m >= 2b it removes at least half
    of the VPU work; at m = b it removes none and adds a launch and a
    matmul.  Both sides are padded as the wrapper pads them."""
    block = min(block, round_up(n, MIN_TILE))
    nb = round_up(n, block) // block
    if round_up(m, MIN_TILE) >= 2 * block:
        return nb, 0
    return 0, nb


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block", "tiles",
                                    "mm_tiles"))
def trsm(u: jax.Array, b: jax.Array, *, block: int = 256,
         interpret: Optional[bool] = None, tiles: Optional[TilePlan] = None,
         mm_tiles: Optional[TilePlan] = None) -> jax.Array:
    """Solve X U = B; U (n, n) upper-triangular, B (m, n).

    ``tiles`` (a trsm :class:`TilePlan`, dim ``block``) overrides the block
    size, the width of the diagonal blocks; ``mm_tiles`` is threaded to
    the update dgemms and the inverse's applies.  A single block with few
    rows is one diagonal kernel.  ``interpret`` defaults to the platform
    (see ``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    block = tile_block(tiles, "trsm", "block", block)
    n0 = u.shape[0]
    m0 = b.shape[0]
    block = min(block, round_up(n0, MIN_TILE))
    u = pad_eye(u, round_up(n0, block))
    b = pad_axes(b, {0: MIN_TILE, 1: block})
    inverted, _ = diag_split(b.shape[0], u.shape[0], block)
    inv = (trsm_diag_inv_pallas(u, block=block, dtype=b.dtype,
                                interpret=interpret) if inverted else None)

    def mm(x, y):
        return matmul(x, y, interpret=interpret, out_dtype=b.dtype,
                      tiles=mm_tiles)

    def solve(i, u, b):
        """X for the diagonal blocks i, i + 1, ... that ``u`` spans."""
        nb = u.shape[0] // block
        if nb == 1:
            if inv is None:
                return trsm_diag_pallas(u, b, interpret=interpret)
            return mm(b, inv[i * block:(i + 1) * block])
        h = (nb // 2) * block
        x1 = solve(i, u[:h, :h], b[:, :h])
        # B_2 -= X_1 U_12: one dgemm with contraction width h
        b2 = b[:, h:] - mm(x1, u[:h, h:])
        return jnp.concatenate([x1, solve(i + nb // 2, u[h:, h:], b2)],
                               axis=1)

    return solve(0, u, b)[:m0, :n0]
