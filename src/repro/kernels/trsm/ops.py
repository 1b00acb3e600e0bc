"""Blocked triangular solve built from the diagonal-block kernel + the MXU
matmul kernel: all O(n^3) off-diagonal work is dgemm-shaped.  Operands
are padded (U identity-extended, B zero-extended) to the block, so every
shape runs through the kernels."""

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
    size; ``mm_tiles`` is threaded to the trailing-update dgemms.
    ``interpret`` defaults to the platform (see ``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    block = tile_block(tiles, "trsm", "block", block)
    n0 = u.shape[0]
    m0 = b.shape[0]
    block = min(block, round_up(n0, MIN_TILE))
    u = pad_eye(u, round_up(n0, block))
    b = pad_axes(b, {0: MIN_TILE, 1: block})
    n = u.shape[0]
    m = b.shape[0]
    nb = n // block
    x_blocks = []
    b_cur = b
    for j in range(nb):
        ujj = jax.lax.slice(u, (j * block, j * block),
                            ((j + 1) * block, (j + 1) * block))
        bj = jax.lax.slice(b_cur, (0, j * block), (m, (j + 1) * block))
        xj = trsm_diag_pallas(ujj, bj, interpret=interpret)
        x_blocks.append(xj)
        if j + 1 < nb:
            # trailing update: B_:,k -= X_:,j @ U_j,k  for k > j (one dgemm)
            u_panel = jax.lax.slice(u, (j * block, (j + 1) * block),
                                    ((j + 1) * block, n))
            upd = matmul(xj, u_panel, interpret=interpret,
                         out_dtype=b_cur.dtype, tiles=mm_tiles)
            tail = jax.lax.slice(b_cur, (0, (j + 1) * block), (m, n)) - upd
            b_cur = jnp.concatenate(
                [jax.lax.slice(b_cur, (0, 0), (m, (j + 1) * block)), tail], axis=1)
    return jnp.concatenate(x_blocks, axis=1)[:m0, :n0]
