"""Plain reference of the dense linear-algebra configuration (float32).

Straightforward ``jax.numpy`` with no kernels, no padding and no process
grid; it imports nothing of the program.  It serves twice:

* the check of the timed calls compares a projection of every answer with
  the same projection worked out from the operands here (``probe`` /
  ``reading`` / ``expected``), at ``Precision.HIGHEST``;
* the control puts these functions in the program's place one precision
  lower (``"bf16_3x"``: three bfloat16 passes, f32 accumulation, which is
  what ``Precision.HIGH`` computes on the TPU's MXU, written out so that
  the CPU computes the same) and must fail the check.  The blocked TRSM
  and Cholesky invert or factor their diagonal blocks on the host in
  float64, so the device's own triangular solves, whose precision is the
  compiler's, never enter.

Every function jits under a caller's sharding: operands sharded over a
mesh give a sharded reference, so the four-chip cell's reference never
gathers a matrix onto one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def dot(x, y, precision: str = "highest"):
    """x @ y in float32 at ``precision``: "highest" (full float32) or
    "bf16_3x" (hi*hi + hi*lo + lo*hi in bfloat16, f32 accumulation)."""
    if precision == "highest":
        return jnp.dot(x, y, precision=HI)
    if precision != "bf16_3x":
        raise ValueError(precision)

    # optimization barriers keep the compiler from folding the float32 ->
    # bfloat16 -> float32 round trips (it may, where excess precision is
    # allowed) and from merging the three products into one
    bar = lax.optimization_barrier

    def split(a):
        hi = bar(a.astype(jnp.bfloat16))
        return hi, bar((a - hi.astype(jnp.float32)).astype(jnp.bfloat16))

    (xh, xl), (yh, yl) = split(x), split(y)

    def mm(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    hh, hl, lh = bar((mm(xh, yh), mm(xh, yl), mm(xl, yh)))
    return hh + (hl + lh)


# -- operands -----------------------------------------------------------------

def operands(key, n: int, names, sharding=None) -> dict:
    """The named operands, made on the device in one call: A, B standard
    normal; U = I + strict-upper N(0, 1/n) (cond ~8); S = G G^T / n + I/10
    (SPD, eigenvalues in [0.1, 4.1]).  The diagonals are kept small so
    that the blocked products carry much of each answer, and a product in
    a lower precision shows in the residual."""
    names = tuple(sorted(set(names)))

    def build(key):
        ka, kb, ku, kg = jax.random.split(key, 4)
        eye = jnp.eye(n, dtype=jnp.float32)
        out = {}
        if "a" in names:
            out["a"] = jax.random.normal(ka, (n, n), jnp.float32)
        if "b" in names:
            out["b"] = jax.random.normal(kb, (n, n), jnp.float32)
        if "u" in names:
            out["u"] = (jnp.triu(jax.random.normal(ku, (n, n), jnp.float32), 1)
                        / math.sqrt(n) + eye)
        if "s" in names:
            g = jax.random.normal(kg, (n, n), jnp.float32)
            out["s"] = jnp.dot(g, g.T, precision=HI) / n + 0.1 * eye
        return out

    shard = None if sharding is None else {k: sharding for k in names}
    return jax.block_until_ready(jax.jit(build, out_shardings=shard)(key))


#: operands of each operation, in call order
OPERANDS = {"matmul": ("a", "b"), "trsm": ("u", "b"), "cholesky": ("s",)}


# -- the operations -----------------------------------------------------------

def matmul(a, b, precision: str = "highest"):
    return dot(a, b, precision)


def _block(n: int) -> int:
    """Column block of the blocked references: 512, and at least four
    blocks, so that their products carry most of the work at every n."""
    return max(1, min(512, n // 4))


def _host_block(x, c0: int, block: int):
    return np.asarray(x[c0:c0 + block, c0:c0 + block], np.float64)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _trsm_step(x, u, b, ujj_inv, c0, precision, block):
    n = u.shape[0]
    t = dot(x, lax.dynamic_slice(u, (0, c0), (n, block)), precision)
    rhs = lax.dynamic_slice(b, (0, c0), (n, block)) - t
    return lax.dynamic_update_slice(x, dot(rhs, ujj_inv, precision),
                                    (0, c0))


def trsm(u, b, precision: str = "highest"):
    """X with X U = B, U upper-triangular: column blocks left to right,
    X_j = (B_j - X U[:, j]) U_jj^{-1} (unsolved columns of X are still 0).
    Each diagonal block is inverted on the host in float64, so every
    product on the device, and no other arithmetic, is in ``precision``."""
    n = u.shape[0]
    block = _block(n)
    x = jnp.zeros_like(b)
    for j in range(n // block):
        c0 = j * block
        inv = np.linalg.inv(np.triu(_host_block(u, c0, block)))
        x = _trsm_step(x, u, b, jnp.asarray(inv, jnp.float32), c0,
                       precision, block)
    return x


@functools.partial(jax.jit, static_argnums=(5, 6))
def _cholesky_step(a, l, lkk, lkk_inv_t, c0, precision, block):
    n = a.shape[0]
    rows = jnp.arange(n)[:, None]
    below = dot(lax.dynamic_slice(a, (0, c0), (n, block)), lkk_inv_t,
                precision)
    below = jnp.where(rows >= c0 + block, below, 0.0)
    l = lax.dynamic_update_slice(
        l, lax.dynamic_update_slice(below, lkk, (c0, 0)), (0, c0))
    return a - dot(below, below.T, precision), l


def cholesky(s, precision: str = "highest"):
    """Lower L with L L^T = S, right-looking by column blocks; each
    diagonal block is factored on the host in float64."""
    n = s.shape[0]
    block = _block(n)
    a, l = s, jnp.zeros_like(s)
    for k in range(n // block):
        c0 = k * block
        akk = _host_block(a, c0, block)
        lkk = np.linalg.cholesky(0.5 * (akk + akk.T))
        a, l = _cholesky_step(a, l, jnp.asarray(lkk, jnp.float32),
                              jnp.asarray(np.linalg.inv(lkk).T, jnp.float32),
                              c0, precision, block)
    return l


OPS = {"matmul": matmul, "trsm": trsm, "cholesky": cholesky}


def compute(op: str, ops: dict, precision: str = "highest"):
    return OPS[op](*(ops[k] for k in OPERANDS[op]), precision)


# -- the check: projections of an answer ----------------------------------------

def probe(key, n: int, cols: int, op: str):
    """The random n x cols block an answer is projected on.  For TRSM and
    Cholesky row j is weighted by (j + 1) / n: the blocked updates pile up
    towards the last columns, where a product in a lower precision shows
    most, and every column still counts."""
    v = jax.random.normal(key, (n, cols), jnp.float32)
    if op == "matmul":
        return v
    return v * ((jnp.arange(n, dtype=jnp.float32) + 1.0) / n)[:, None]


def reading(op: str, out, ops: dict, key, cols: int) -> dict:
    """What is read of one answer while it exists: its projection on a
    fresh probe V (matmul: C V; TRSM: X (U V); Cholesky: L (L^T V)), and
    for Cholesky the largest entry above the diagonal."""
    v = probe(key, out.shape[0], cols, op)
    if op == "matmul":
        return {"proj": jnp.dot(out, v, precision=HI)}
    if op == "trsm":
        return {"proj": jnp.dot(out, jnp.dot(ops["u"], v, precision=HI),
                                precision=HI)}
    if op == "cholesky":
        return {"proj": jnp.dot(out, jnp.dot(out.T, v, precision=HI),
                                precision=HI),
                "upper": jnp.max(jnp.abs(jnp.triu(out, 1)))}
    raise ValueError(op)


def expected(op: str, ops: dict, key, cols: int):
    """The same projection worked out from the operands: A (B V), B V and
    S V, at full float32 precision."""
    n = next(iter(ops.values())).shape[0]
    v = probe(key, n, cols, op)
    if op == "matmul":
        return jnp.dot(ops["a"], jnp.dot(ops["b"], v, precision=HI),
                       precision=HI)
    if op == "trsm":
        return jnp.dot(ops["b"], v, precision=HI)
    if op == "cholesky":
        return jnp.dot(ops["s"], v, precision=HI)
    raise ValueError(op)


def residual(proj, want):
    """||proj - want||_F / ||want||_F."""
    return jnp.linalg.norm(proj - want) / jnp.linalg.norm(want)
