"""Shared wrapper plumbing for the Pallas kernel families.

The per-family ``ops.py`` wrappers all did the same three things with
copy-pasted code: round dimensions up to a block multiple, ``jnp.pad``
operands out to the rounded shape (unconditionally, even when already
aligned), and hard-code the block sizes.  This module centralizes the
first two and routes the third through ``repro.perf.kernel``: a wrapper
takes an optional :class:`TilePlan` (frozen/hashable, so it rides along
as a jit-static argument) and falls back to the historical heuristic
blocks when none is given.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

# the model layer is pure numpy — importing it pulls no jax machinery in
from ..perf.kernel import (MIN_TILE, TilePlan, VMEM_BUDGET,
                           heuristic_matmul_blocks, heuristic_plan)

__all__ = [
    "MIN_TILE", "TilePlan", "VMEM_BUDGET", "heuristic_matmul_blocks",
    "heuristic_plan", "pad_axes", "pad_eye", "resolve_interpret",
    "round_up", "tile_block",
]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` as given, else derived from the platform: the Pallas
    interpreter off the TPU, the compiled kernel on it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return (x + m - 1) // m * m


def pad_axes(x: jax.Array,
             multiples: Mapping[int, int]) -> jax.Array:
    """Zero-pad ``x`` so every listed axis is a multiple of its block.

    ``multiples`` maps axis index -> block size.  Returns ``x`` unchanged
    (no ``jnp.pad`` issued at all) when every axis is already aligned.
    """
    width: list = [(0, 0)] * x.ndim
    any_pad = False
    for axis, m in multiples.items():
        extent = x.shape[axis]
        pad = round_up(extent, m) - extent
        if pad:
            width[axis] = (0, pad)
            any_pad = True
    if not any_pad:
        return x
    return jnp.pad(x, width)


def pad_eye(x: jax.Array, size: int) -> jax.Array:
    """blockdiag(x, I) of edge ``size``: the structure-preserving pad for
    triangular and SPD operands (the padded system's solution is the
    original one, zero-extended)."""
    n = x.shape[0]
    if size == n:
        return x
    out = jnp.pad(x, ((0, size - n), (0, size - n)))
    idx = jnp.arange(n, size)
    return out.at[idx, idx].set(jnp.ones((), x.dtype))


def tile_block(tiles: Optional[TilePlan], kernel: str, dim: str,
               default: Union[int, Tuple[int, ...]]):
    """Block size for ``dim`` out of a plan, or the caller's default.

    Raises if the plan targets a different kernel family — a swapped
    plan would otherwise silently run with nonsense blocks.
    """
    if tiles is None:
        return default
    if tiles.kernel != kernel:
        raise ValueError(f"TilePlan for {tiles.kernel!r} passed to "
                         f"{kernel!r} wrapper")
    return tiles[dim]
