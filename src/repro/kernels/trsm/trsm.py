"""Triangular-solve Pallas kernel:  X U = B  with U upper-triangular.

TPU adaptation (DESIGN.md §3): a triangular solve's column recurrence maps
poorly onto the MXU, so the kernel only performs the *diagonal-block*
back-substitution (a ``bu x bu`` block held in VMEM, column loop on the
VPU), while the ops.py wrapper solves the full system by recursive halving
over the diagonal blocks, so that all O(n^3) off-diagonal work runs
through the MXU matmul kernel.  This mirrors how LibSci's dtrsm spends
its flops in dgemm-shaped updates (paper Fig. 1 shows dtrsm below dgemm
efficiency for the same reason).  Where B has many rows against the
block's width, the wrapper does not substitute them at all: it inverts
each diagonal block once (:func:`trsm_diag_inv_pallas`, the same column
loop on the identity's rows) and applies the inverse on the MXU.

The column loop is right-looking and touches no dynamic slice: column k
of the working block and row k of U are picked out with iota masks and
exact masked reductions, the rank-1 update runs on whole VMEM tiles, and
the solved column overwrites column k of the f32 working block, which
then holds X.  Everything is computed in float32 on the VPU.  A row whose
right-hand side is zero in columns < k stays exactly zero there, so the
inverse of an upper-triangular block comes out exactly upper-triangular.

Grid: (M/bm,) row blocks of B, each solved independently against U; for
the inverse, (blocks, bu/bm) row blocks of each diagonal block's identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _substitute(u_ref, w_ref, *, nb: int, start=0):
    """Back-substitute the f32 working block ``w_ref`` (bm, nb) against
    (nb, nb) U in place, from column ``start`` on: it then holds X."""
    bm = w_ref.shape[0]

    def body(k, carry):
        u = u_ref[...].astype(jnp.float32)
        u_row = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
        lane = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
        col = lax.broadcasted_iota(jnp.int32, (bm, nb), 1)
        urow = jnp.sum(jnp.where(u_row == k, u, 0.0), axis=0,
                       keepdims=True)                           # U[k, :]
        ukk = jnp.sum(jnp.where(lane == k, urow, 0.0), axis=1,
                      keepdims=True)                            # (1, 1)
        w = w_ref[...]
        xk = jnp.sum(jnp.where(col == k, w, 0.0), axis=1,
                     keepdims=True) / ukk                       # (bm, 1)
        # B[:, j] -= x_k u_kj for j > k; column k becomes X[:, k]
        upd = w - xk * jnp.where(lane > k, urow, 0.0)
        w_ref[...] = jnp.where(col == k, xk, upd)
        return carry

    lax.fori_loop(start, nb, body, 0)


def _trsm_diag_kernel(u_ref, b_ref, x_ref, w_ref, *, nb: int):
    """Back-substitution of one (bm, nb) block of B against (nb, nb) U."""
    w_ref[...] = b_ref[...].astype(jnp.float32)
    _substitute(u_ref, w_ref, nb=nb)
    x_ref[...] = w_ref[...].astype(x_ref.dtype)


def _trsm_inv_kernel(u_ref, x_ref, w_ref, *, nb: int):
    """Rows [r bm, (r + 1) bm) of U^{-1} for one (nb, nb) diagonal block:
    X U = I on those rows of the identity.  Their columns < r bm are zero
    and stay zero, so the loop starts at column r bm."""
    bm = w_ref.shape[0]
    r0 = pl.program_id(1) * bm
    row = lax.broadcasted_iota(jnp.int32, (bm, nb), 0) + r0
    col = lax.broadcasted_iota(jnp.int32, (bm, nb), 1)
    w_ref[...] = jnp.where(row == col, 1.0, 0.0).astype(jnp.float32)
    _substitute(u_ref, w_ref, nb=nb, start=r0)
    x_ref[...] = w_ref[...].astype(x_ref.dtype)


def _row_block(bm: int, m: int) -> int:
    """The largest row block <= bm that divides m (m % 8 == 0)."""
    bm = min(bm, m)
    while m % bm != 0 and bm > 8:
        bm //= 2
    return bm


def trsm_diag_pallas(u: jax.Array, b: jax.Array, *, bm: int = 256,
                     interpret: bool = False) -> jax.Array:
    """Solve X U = B for one diagonal block U (nb x nb, upper-triangular,
    nb <= ~512 so U fits VMEM); B is (M, nb) with M % bm == 0."""
    nb = u.shape[0]
    m = b.shape[0]
    bm = _row_block(bm, m)
    assert u.shape == (nb, nb) and b.shape[1] == nb and m % bm == 0
    return pl.pallas_call(
        functools.partial(_trsm_diag_kernel, nb=nb),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((bm, nb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, nb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, nb), jnp.float32)],
        interpret=interpret,
        name="trsm",  # the operation's name in a profiler trace
    )(u, b)


def trsm_diag_inv_pallas(u: jax.Array, *, block: int, bm: int = 256,
                         dtype=None, interpret: bool = False) -> jax.Array:
    """The inverse of every (block x block) diagonal block of U (n x n,
    upper-triangular, n % block == 0) in one call: an (n, block) stack
    whose rows [i block, (i + 1) block) hold U_ii^{-1}, exactly zero below
    its diagonal.  ``dtype`` is the output's (default U's)."""
    n = u.shape[0]
    assert u.shape == (n, n) and n % block == 0
    bm = _row_block(bm, block)
    per = block // bm
    return pl.pallas_call(
        functools.partial(_trsm_inv_kernel, nb=block),
        grid=(n // block, per),
        in_specs=[pl.BlockSpec((block, block), lambda i, r: (i, i))],
        out_specs=pl.BlockSpec((bm, block), lambda i, r: (i * per + r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, block), dtype or u.dtype),
        scratch_shapes=[pltpu.VMEM((bm, block), jnp.float32)],
        interpret=interpret,
        name="trsm",  # the operation's name in a profiler trace
    )(u)
