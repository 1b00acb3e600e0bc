"""Single-block Cholesky factorization Pallas kernel.

TPU adaptation: dpotrf's scalar column recurrence has no MXU shape, so —
as with dtrsm — the kernel factors only a VMEM-resident diagonal block
(rank-1 updates on the VPU, one column per step), and ops.py blocks the
full factorization so panel solves and trailing (syrk) updates run through
the trsm/matmul kernels on the MXU.

Like LAPACK's dpotrf the kernel reads only the lower triangle: it mirrors
it into an exactly symmetric f32 working block, so column k and row k of
the trailing matrix are the same numbers and the rank-1 update needs no
transpose.  Column k and row k are picked out with iota masks and exact
masked reductions (no dynamic slice); the scaled column overwrites column
k of the working block, whose lower triangle then holds L.

One grid step per call (the block is the whole problem for the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chol_kernel(a_ref, l_ref, w_ref, *, nb: int):
    a = a_ref[...].astype(jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    col = lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    w_ref[...] = jnp.where(row >= col, a, a.T)

    def body(k, carry):
        row = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
        col = lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
        sub = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
        lane = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
        w = w_ref[...]
        colk = jnp.sum(jnp.where(col == k, w, 0.0), axis=1, keepdims=True)
        rowk = jnp.sum(jnp.where(row == k, w, 0.0), axis=0, keepdims=True)
        d = jnp.sqrt(jnp.sum(jnp.where(sub == k, colk, 0.0), axis=0,
                             keepdims=True))                    # (1, 1)
        lcol = colk / d
        lrow = rowk / d
        # trailing rank-1 update on rows and columns > k only
        upd = w - jnp.where(sub > k, lcol, 0.0) * jnp.where(lane > k, lrow,
                                                             0.0)
        w_ref[...] = jnp.where(col == k, jnp.where(sub >= k, lcol, 0.0), upd)
        return carry

    lax.fori_loop(0, nb, body, 0)
    l_ref[...] = jnp.where(row >= col, w_ref[...], 0.0).astype(l_ref.dtype)


def cholesky_block_pallas(a: jax.Array, *, interpret: bool = False) -> jax.Array:
    """L with L L^T = A for one SPD block (nb x nb, nb <= ~512)."""
    nb = a.shape[0]
    assert a.shape == (nb, nb)
    return pl.pallas_call(
        functools.partial(_chol_kernel, nb=nb),
        grid=(1,),
        in_specs=[pl.BlockSpec((nb, nb), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((nb, nb), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, nb), a.dtype),
        scratch_shapes=[pltpu.VMEM((nb, nb), jnp.float32)],
        interpret=interpret,
        name="cholesky",  # the operation's name in a profiler trace
    )(a)
