"""Cannon's matrix-multiplication algorithm on a JAX device mesh.

Executable counterparts of the paper's models (§V-A):

* ``cannon_2d``        — p = g*g processes, initial skew + g-step shift loop.
* ``cannon_2d_ovlp``   — same, loop restructured so the iteration-(i+1)
  shifts have no data dependency on iteration-i's matmul: XLA's latency-
  hiding scheduler may overlap them (the UPC version used async copies; on
  TPU this is the idiomatic equivalent — see DESIGN.md §3).
* ``cannon_25d``/``_ovlp`` — c replication layers; each layer executes a
  contiguous chunk of s = g/c of the g shift steps starting from its own
  skew offset, partial C combined with a psum over the layer axis (the
  model's ``T_reduce`` term).  Inputs arrive replicated over layers (the
  replication itself is the ``T_iniRepl`` term and is exercised/charged by
  the driver when it distributes operands).

The initial skew (block (i,j) -> (i, j-i)) is rank-dependent, which a
static ``ppermute`` cannot express per-axis — but it *is* a fixed
permutation of the flattened (row, col) grid, so we issue one ppermute over
the joint axes.  The non-overlapped variants place an
``optimization_barrier`` between matmul and the next shift to pin the
serialized schedule (making 2D-vs-overlap measurable on real hardware).

All local matmuls go through ``local_mm`` so the Pallas kernel
(repro.kernels.matmul) can be swapped in for the jnp default.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .grid import grid_size, n_layers

MatMul = Callable[[jax.Array, jax.Array], jax.Array]


def _default_mm(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST)


def _skew_perm(g: int, axis_is_row: bool, offset_sign: int, extra: int = 0,
               layers: int = 1, s: int = 1):
    """Permutation of the flattened (lyr, row, col) grid implementing the
    Cannon skew: A block (i, j) -> (i, j - i - l*s); B block (i, j) ->
    (i - j - l*s, j).  ``offset_sign`` folds direction."""
    perm = []
    for l in range(layers):
        for i in range(g):
            for j in range(g):
                src = (l * g + i) * g + j
                off = (i if axis_is_row else j) + l * s
                if axis_is_row:
                    dst = (l * g + i) * g + ((j - off) % g)
                else:
                    dst = (l * g + ((i - off) % g)) * g + j
                perm.append((src, dst))
    return perm


def _shift_perm(g: int):
    """Uniform shift by one (ring) on one axis."""
    return [(k, (k - 1) % g) for k in range(g)]


def _cannon_body(a, b, *, g: int, steps: int, layers: int, s: int,
                 local_mm: MatMul, overlap: bool):
    grid_axes = ("lyr", "row", "col") if layers > 1 else ("row", "col")
    a = lax.ppermute(a, grid_axes, _skew_perm(g, True, 1, layers=layers, s=s))
    b = lax.ppermute(b, grid_axes, _skew_perm(g, False, 1, layers=layers, s=s))
    c = local_mm(a, b)

    shift_a = _shift_perm(g)
    shift_b = _shift_perm(g)

    def step(carry, _):
        a, b, c = carry
        if overlap:
            # comm for iteration i+1 is independent of the current matmul
            a_nxt = lax.ppermute(a, "col", shift_a)
            b_nxt = lax.ppermute(b, "row", shift_b)
            c = c + local_mm(a_nxt, b_nxt)
            return (a_nxt, b_nxt, c), None
        # serialized: shifts wait for the previous matmul
        a, b, c = lax.optimization_barrier((a, b, c))
        a = lax.ppermute(a, "col", shift_a)
        b = lax.ppermute(b, "row", shift_b)
        c = c + local_mm(a, b)
        return (a, b, c), None

    if steps > 1:
        (a, b, c), _ = lax.scan(step, (a, b, c), None, length=steps - 1)
    if layers > 1:
        c = lax.psum(c, "lyr")
    return c


def _make(mesh, *, overlap: bool, local_mm: Optional[MatMul] = None,
          check_vma: bool = True):
    g = grid_size(mesh)
    c_layers = n_layers(mesh)
    if c_layers > 1 and g % c_layers != 0:
        raise ValueError(f"layers c={c_layers} must divide grid g={g}")
    s = g // c_layers if c_layers > 1 else g
    mm = local_mm or _default_mm
    in_spec = P("row", "col")  # replicated over lyr when present

    fn = functools.partial(_cannon_body, g=g, steps=s, layers=c_layers, s=s,
                           local_mm=mm, overlap=overlap)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(in_spec, in_spec), out_specs=in_spec,
        check_vma=check_vma))


def make(mesh, variant: str, *, local_mm: Optional[MatMul] = None,
         check_vma: bool = True):
    """Reusable compiled executor: (A, B) -> C for the given variant (the
    2d/2.5d split is carried by the mesh's layer axis).  ``check_vma=False``
    for locals the varying-axis checker cannot type (Pallas kernels)."""
    return _make(mesh, overlap=variant.endswith("ovlp"), local_mm=local_mm,
                 check_vma=check_vma)


def cannon_2d(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    """C = A @ B on a ("row","col") mesh; A, B block-distributed."""
    return _make(mesh, overlap=False, local_mm=local_mm)(A, B)


def cannon_2d_ovlp(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=True, local_mm=local_mm)(A, B)


def cannon_25d(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    """C = A @ B on a ("lyr","row","col") mesh; operands replicated over
    layers; each layer computes s = g/c of the shift steps."""
    return _make(mesh, overlap=False, local_mm=local_mm)(A, B)


def cannon_25d_ovlp(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=True, local_mm=local_mm)(A, B)
