"""Triangular-solve Pallas kernel:  X U = B  with U upper-triangular.

TPU adaptation (DESIGN.md §3): a triangular solve's column recurrence maps
poorly onto the MXU, so the kernel only performs the *diagonal-block*
back-substitution (a ``bu x bu`` block held in VMEM, column loop on the
VPU), while the ops.py wrapper solves the full system by recursive halving
over the diagonal blocks, so that all O(n^3) off-diagonal work runs
through the MXU matmul kernel.  This mirrors how LibSci's dtrsm spends
its flops in dgemm-shaped updates (paper Fig. 1 shows dtrsm below dgemm
efficiency for the same reason).

The column loop is right-looking and touches no dynamic slice: column k
of the working block and row k of U are picked out with iota masks and
exact masked reductions, the rank-1 update runs on whole VMEM tiles, and
the solved column overwrites column k of the f32 working block, which
then holds X.  Everything is computed in float32 on the VPU.

Grid: (M/bm,) row blocks of B, each solved independently against U.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _trsm_diag_kernel(u_ref, b_ref, x_ref, w_ref, *, nb: int):
    """Back-substitution of one (bm, nb) block of B against (nb, nb) U."""
    w_ref[...] = b_ref[...].astype(jnp.float32)
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

    lax.fori_loop(0, nb, body, 0)
    x_ref[...] = w_ref[...].astype(x_ref.dtype)


def trsm_diag_pallas(u: jax.Array, b: jax.Array, *, bm: int = 256,
                     interpret: bool = False) -> jax.Array:
    """Solve X U = B for one diagonal block U (nb x nb, upper-triangular,
    nb <= ~512 so U fits VMEM); B is (M, nb) with M % bm == 0."""
    nb = u.shape[0]
    m = b.shape[0]
    bm = min(bm, m)
    while m % bm != 0 and bm > 8:       # largest row block dividing M
        bm //= 2
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
