"""jit'd public wrapper for the matmul kernel: pads arbitrary shapes to
block multiples and resolves block sizes from an explicit
:class:`TilePlan` (or the VMEM-fitting heuristic when none is given).
Blocks are capped at each dimension's 128-padded extent, so small
problems run as one padded block instead of leaving the kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from ..common import (MIN_TILE, TilePlan, heuristic_matmul_blocks, pad_axes,
                      resolve_interpret, round_up)
from .matmul import matmul_pallas


def _pick_blocks(m: int, n: int, k: int, bytes_per_el: int,
                 vmem_budget: Optional[int] = None):
    """Heuristic block choice (start 256x256x512, shrink to fit).  The
    budget is overridable per call; the shrink loop bails at the 128 floor
    instead of spinning when even the floor blocks exceed the budget."""
    return heuristic_matmul_blocks(m, n, k, bytes_per_el,
                                   vmem_budget=vmem_budget)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "out_dtype", "tiles"))
def matmul(a: jax.Array, b: jax.Array, *, interpret: Optional[bool] = None,
           out_dtype=None, tiles: Optional[TilePlan] = None) -> jax.Array:
    """C = A @ B for any (M, K) x (K, N).

    ``interpret`` defaults to the platform: the Pallas interpreter off the
    TPU, the compiled kernel on it.  ``tiles`` is a matmul
    :class:`TilePlan` (dims bm/bn/bk); omitted, the historical heuristic
    blocks are used.
    """
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    if tiles is not None:
        if tiles.kernel != "matmul":
            raise ValueError(f"TilePlan for {tiles.kernel!r} passed to matmul")
        bm, bn, bk = tiles["bm"], tiles["bn"], tiles["bk"]
    else:
        bm, bn, bk = _pick_blocks(m, n, k, a.dtype.itemsize)
    bm = min(bm, round_up(m, MIN_TILE))
    bn = min(bn, round_up(n, MIN_TILE))
    bk = min(bk, round_up(k, MIN_TILE))
    ap = pad_axes(a, {0: bm, 1: bk})
    bp = pad_axes(b, {0: bk, 1: bn})
    out = matmul_pallas(ap, bp, bm=bm, bn=bn, bk=bk,
                        interpret=resolve_interpret(interpret),
                        out_dtype=out_dtype)
    return out[:m, :n]
