"""MXU-tiled blocked matmul Pallas kernel (the framework's dgemm).

Tiling: grid (M/bm, N/bn, K/bk) with the contraction dimension innermost —
TPU grids execute sequentially, so a VMEM f32 scratch accumulator carries
partial sums across the K steps of one (i, j) tile; the output is written
once, on the last K step (revisiting semantics).

Block sizes default to (256, 256, 512): A-block 256x512 + B-block 512x256
bf16 = 0.5 MB and the f32 accumulator 0.25 MB comfortably fit VMEM while
keeping every matmul dimension a multiple of the 128x128 MXU tile.  The
kernel compiles under the chip's scoped-VMEM default, which its machine
profile states as ``vmem_bytes`` for the tile model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, precision):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=precision)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, *,
                  bm: int = 256, bn: int = 256, bk: int = 512,
                  interpret: bool = False,
                  out_dtype=None) -> jax.Array:
    """C = A @ B; shapes (M, K) x (K, N), dimensions multiples of blocks
    (the ops.py wrapper pads arbitrary shapes)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    out_dtype = out_dtype or a.dtype
    # float32 operands contract at full float32 precision (the MXU's
    # multi-pass mode); bfloat16 products are exact in the default mode
    precision = (lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, precision=precision),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul",  # the operation's name in a profiler trace
    )(a, b)
