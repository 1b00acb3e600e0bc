"""SUMMA on a JAX device mesh (executable counterpart of §V models).

Per step k: the owners of A's k-th block column broadcast their block along
grid rows, the owners of B's k-th block row broadcast along grid columns,
then every process accumulates a local matmul.  The broadcast is a
select-and-reduce (mask the owner, psum over the axis) — the same
collective GSPMD emits for a one-to-many transfer on a mesh axis.

2.5D: c layers each execute the contiguous chunk of s = g/c of the g steps
(offset l*s), partial C psum-combined over the layer axis.

The overlap variants prefetch the panels for step k+1 before the local
matmul of step k (no data dependency => the scheduler may overlap); the
non-overlapped variants pin serialization with an optimization_barrier.
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


def _panels(a, b, k):
    """Select-and-reduce broadcasts of A's block-col k / B's block-row k."""
    col = lax.axis_index("col")
    row = lax.axis_index("row")
    a_panel = lax.psum(jnp.where(col == k, a, jnp.zeros_like(a)), "col")
    b_panel = lax.psum(jnp.where(row == k, b, jnp.zeros_like(b)), "row")
    return a_panel, b_panel


def _summa_body(a, b, *, steps: int, layers: int, s: int,
                local_mm: MatMul, overlap: bool):
    base = lax.axis_index("lyr") * s if layers > 1 else 0

    if overlap:
        ap, bp = _panels(a, b, base)

        def step(carry, k):
            c, ap, bp = carry
            # prefetch panels for k+1 (wraps harmlessly on the last step)
            ap_nxt, bp_nxt = _panels(a, b, jnp.minimum(k + 1, base + steps - 1))
            c = c + local_mm(ap, bp)
            return (c, ap_nxt, bp_nxt), None

        c0 = jnp.zeros_like(local_mm(ap, bp))
        (c, ap, bp), _ = lax.scan(step, (c0, ap, bp),
                                  base + jnp.arange(steps - 1))
        c = c + local_mm(ap, bp)
    else:
        def step(carry, k):
            c = carry
            c = lax.optimization_barrier(c)
            ap, bp = _panels(a, b, k)
            return c + local_mm(ap, bp), None

        ap0, bp0 = _panels(a, b, base)
        c0 = jnp.zeros_like(local_mm(ap0, bp0))
        c, _ = lax.scan(step, c0, base + jnp.arange(steps))

    if layers > 1:
        c = lax.psum(c, "lyr")
    return c


def _make(mesh, *, overlap: bool, local_mm: Optional[MatMul] = None,
          check_vma: bool = True):
    g = grid_size(mesh)
    layers = n_layers(mesh)
    if layers > 1 and g % layers != 0:
        raise ValueError(f"layers c={layers} must divide grid g={g}")
    s = g // layers if layers > 1 else g
    fn = functools.partial(_summa_body, steps=s, layers=layers, s=s,
                           local_mm=local_mm or _default_mm, overlap=overlap)
    spec = P("row", "col")
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=check_vma))


def make(mesh, variant: str, *, local_mm: Optional[MatMul] = None,
         check_vma: bool = True):
    """Reusable compiled executor: (A, B) -> C for the given variant (the
    2d/2.5d split is carried by the mesh's layer axis).  ``check_vma=False``
    for locals the varying-axis checker cannot type (Pallas kernels)."""
    return _make(mesh, overlap=variant.endswith("ovlp"), local_mm=local_mm,
                 check_vma=check_vma)


def summa_2d(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=False, local_mm=local_mm)(A, B)


def summa_2d_ovlp(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=True, local_mm=local_mm)(A, B)


def summa_25d(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=False, local_mm=local_mm)(A, B)


def summa_25d_ovlp(A, B, *, mesh, local_mm: Optional[MatMul] = None):
    return _make(mesh, overlap=True, local_mm=local_mm)(A, B)
