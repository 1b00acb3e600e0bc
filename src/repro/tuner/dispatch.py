"""Model-guided dispatch: the executable back half of the tuner.

``matmul`` / ``trsm`` / ``cholesky`` take *global* (unsharded) operands,
ask the :class:`~repro.tuner.autotune.Tuner` for an
:class:`~repro.tuner.plan.ExecutionPlan`, build the planned 2D / 2.5D
process-grid mesh, block-distribute the operands (padding to the grid where
needed — identity-extended for triangular/SPD structure), and run the
chosen ``shard_map`` variant with the planned local kernels:

* ``local_kernel="pallas"`` wires the Pallas kernels
  (``kernels.matmul/trsm/cholesky``) in as the local matmul / triangular
  solve / diagonal factor (compiled on the TPU, interpreted elsewhere);
* ``local_kernel="jnp"`` (the CPU default) uses the ``jnp.dot`` /
  ``jax.scipy`` locals.

Meshes and compiled executors are memoized per (grid, devices, variant,
kernel), so a cache-hit call pays only plan lookup + padding + dispatch.

Each call writes host spans into any profiler trace taken of the process
(``repro.obs.maybe_span``): ``repro.linalg.<op>`` around the whole call,
and inside it ``repro.dispatch.plan``, ``repro.dispatch.distribute``
(padding and distribution) and ``repro.dispatch.execute`` (executor
launch and the result slice).  They end when the work is launched, not
when the device finishes it.  With Pallas locals, a TRSM or Cholesky
execute span carries ``diag_inverted`` / ``diag_substituted``: how many
diagonal blocks one device's local solves invert and apply on the MXU,
and how many they back-substitute, in one call (from shapes alone).

When telemetry recording is on (``REPRO_TELEMETRY=1`` /
``repro.telemetry.enable()`` / per-call ``observe=True``) every dispatch
emits one measured :class:`~repro.telemetry.RunRecord` with per-phase
wall times (plan / distribute / execute, the execute phase blocked to
completion) tagged by the plan's machine fingerprint — the raw material
of the measured-run feedback loop.  Only that record blocks on the
result; spans never do.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels.cholesky.ops import cholesky as _kchol
from ..kernels.cholesky.ops import diag_split as _chol_split
from ..kernels.common import TilePlan, pad_eye
from ..kernels.matmul.ops import matmul as _kmm
from ..kernels.trsm.ops import diag_split as _trsm_split
from ..kernels.trsm.ops import trsm as _ktrsm
# NB: import the factories, not the modules — the linalg package shadows
# the trsm/cholesky module attributes with the dispatch wrappers.
from ..linalg.cannon import make as _make_cannon
from ..linalg.cholesky import make as _make_cholesky
from ..linalg.grid import distribute, make_grid_mesh
from ..linalg.summa import make as _make_summa
from ..linalg.trsm import make as _make_trsm
from .. import obs
from .autotune import Tuner, default_tuner
from .plan import ExecutionPlan

_LOCK = threading.Lock()
_MESHES: Dict[tuple, jax.sharding.Mesh] = {}
_EXECUTORS: Dict[tuple, object] = {}


# -- local kernel hooks -----------------------------------------------------
# Hook closures are built per (algo, kernel, interpret, tiles) executor key
# — the memo in _executor keeps their identity stable, so shard_map never
# re-traces for a configuration it has already compiled.

def _tiles_key(tiles: Dict[str, Dict[str, int]]) -> tuple:
    """Canonical hashable form of a plan's tiles map (executor memo key)."""
    return tuple(sorted((fam, tuple(sorted(blocks.items())))
                        for fam, blocks in (tiles or {}).items()))


def _tile_plans(tiles: Dict[str, Dict[str, int]]) -> Dict[str, TilePlan]:
    """The plan's JSON tile map as jit-static TilePlan objects."""
    return {fam: TilePlan.from_blocks(fam, blocks, source="plan")
            for fam, blocks in (tiles or {}).items()}


def _local_hooks(algo: str, local_kernel: str, interpret: bool,
                 tiles: Optional[Dict[str, Dict[str, int]]] = None) -> dict:
    if local_kernel != "pallas":
        return {}
    plans = _tile_plans(tiles)
    mm_tp = plans.get("matmul")
    trsm_tp = plans.get("trsm")
    chol_tp = plans.get("cholesky")

    def local_mm(a, b):
        return _kmm(a, b, interpret=interpret, out_dtype=a.dtype,
                    tiles=mm_tp)

    if algo in ("cannon", "summa"):
        return {"local_mm": local_mm}
    if algo == "trsm":
        def local_solve(b, u):
            return _ktrsm(u, b, interpret=interpret, tiles=trsm_tp,
                          mm_tiles=mm_tp)
        return {"local_mm": local_mm, "local_solve": local_solve}
    if algo == "cholesky":
        def local_chol(a):
            return _kchol(a, interpret=interpret, tiles=chol_tp,
                          mm_tiles=mm_tp)

        def local_panel_solve(a, ljj):
            # panel width is fixed by the diagonal factor's extent; only
            # the dgemm tail inherits a tile choice here
            return _ktrsm(ljj.T, a, interpret=interpret, mm_tiles=mm_tp)
        return {"local_mm": local_mm, "local_chol": local_chol,
                "local_solve": local_panel_solve}
    raise ValueError(algo)


_MAKERS = {"cannon": _make_cannon, "summa": _make_summa, "trsm": _make_trsm,
           "cholesky": _make_cholesky}


def _mesh_for(g: int, c: int, devices: Tuple) -> jax.sharding.Mesh:
    key = (g, c, tuple(d.id for d in devices))
    with _LOCK:
        mesh = _MESHES.get(key)
    if mesh is None:
        mesh = make_grid_mesh(g, g, layers=c, devices=list(devices))
        with _LOCK:
            _MESHES[key] = mesh
    return mesh


def _executor(plan: ExecutionPlan, mesh, devices: Tuple, interpret: bool):
    key = (plan.algo, plan.variant, plan.g, plan.c,
           tuple(d.id for d in devices), plan.local_kernel, interpret,
           _tiles_key(plan.tiles))
    with _LOCK:
        fn = _EXECUTORS.get(key)
    if fn is None:
        hooks = _local_hooks(plan.algo, plan.local_kernel, interpret,
                             plan.tiles)
        # the varying-axis checker cannot type a Pallas kernel's body (the
        # interpreter evaluates it inside the shard_map), so executors with
        # Pallas locals are built unchecked
        fn = _MAKERS[plan.algo](mesh, plan.variant, check_vma=not hooks,
                                **hooks)
        with _LOCK:
            if len(_EXECUTORS) > 64:
                _EXECUTORS.clear()
            _EXECUTORS[key] = fn
    return fn


# -- padding ----------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_zero(x, rows: int, cols: int):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _check_square(name: str, x) -> int:
    if x.ndim != 2 or x.shape[0] != x.shape[1]:
        raise ValueError(f"{name} must be square 2-D, got {x.shape} "
                         "(the paper's algorithms are square-grid)")
    return int(x.shape[0])


def _dtype_key(x) -> str:
    """Plan-cache dtype key without staging the operand to device (x64
    inputs canonicalize the same way jnp.asarray would convert them)."""
    return str(jax.dtypes.canonicalize_dtype(np.result_type(x)))


# -- execution --------------------------------------------------------------

def _resolve(devices: Optional[Sequence], plan_p: int) -> Tuple:
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < plan_p:
        raise ValueError(f"plan needs {plan_p} devices, have {len(devices)}")
    return tuple(devices[:plan_p])


def executor(plan: ExecutionPlan, devices: Optional[Sequence] = None):
    """The plan's memoized jitted executor and the mesh it runs on — what
    :func:`execute` calls on the distributed operands (lower it to inspect
    or pre-compile the program).  Pallas locals are compiled on the TPU
    and interpreted elsewhere."""
    devs = _resolve(devices, plan.p)
    interpret = devs[0].platform != "tpu"
    mesh = _mesh_for(plan.g, plan.c, devs)
    return _executor(plan, mesh, devs, interpret), mesh


def _diag_args(plan: ExecutionPlan) -> dict:
    """``diag_inverted`` / ``diag_substituted`` of the plan's local Pallas
    solves on one device in one call, by the kernels' own shape rule: each
    of the g steps solves a local (rows, edge) block, and on a grid a
    Cholesky step also solves its panel against L_jj^T at the wrapper's
    default block (on one device that solve is masked out, and dead)."""
    if plan.local_kernel != "pallas" or plan.algo not in ("trsm",
                                                          "cholesky"):
        return {}
    g, c = plan.g, plan.c
    edge = _round_up(plan.n, g) // g
    block = (plan.tiles or {}).get(plan.algo, {}).get("block", 256)
    if plan.algo == "trsm":
        rows = _round_up(plan.n, c * g) // (c * g)
        split = _trsm_split(rows, edge, block)
    else:
        split = _chol_split(edge, block)
        if g > 1:
            panel = _trsm_split(edge, edge)
            split = (split[0] + panel[0], split[1] + panel[1])
    return {"diag_inverted": g * split[0], "diag_substituted": g * split[1]}


@contextmanager
def _phase(pt, plan: ExecutionPlan, name: str, **args):
    """The ``repro.dispatch.<name>`` span, paired with the plan's
    prediction for the phase; with a telemetry timer, the phase's wall
    seconds are added to it as well."""
    pred = plan.predicted.get(name)
    if pred is None and name == "execute":
        pred = plan.predicted.get("total")
    with obs.maybe_span(f"dispatch.{name}", cat="dispatch",
                        predicted_s=pred, **args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if pt is not None:
                pt.add(name, time.perf_counter() - t0)


def execute(plan: ExecutionPlan, *operands,
            devices: Optional[Sequence] = None, observe: bool = False,
            store=None, _plan_seconds: float = 0.0):
    """Run an already-resolved plan on its operands (benchmarks use this to
    force specific — including deliberately bad — variants).

    ``observe=True`` records this run's measured phases into the telemetry
    store even when global recording is off; ``store`` routes the record
    (default: the global default store).  ``_plan_seconds`` lets the
    model-guided wrappers account the planning time they already spent."""
    from .. import telemetry
    fn, mesh = executor(plan, devices)
    pt = None
    if observe or telemetry.enabled():
        pt = telemetry.timer_for_plan(plan, kind="dispatch")
        if _plan_seconds > 0.0:
            pt.add("plan", _plan_seconds)
    n = plan.n
    g, c = plan.g, plan.c
    with _phase(pt, plan, "distribute", algo=plan.algo):
        if plan.algo in ("cannon", "summa"):
            a, b = (jnp.asarray(x) for x in operands)
            m = _round_up(n, g)
            args = (distribute(_pad_zero(a, m, m), mesh, P("row", "col")),
                    distribute(_pad_zero(b, m, m), mesh, P("row", "col")))
        elif plan.algo == "trsm":
            u, b = (jnp.asarray(x) for x in operands)
            m = _round_up(n, g)
            mb = _round_up(n, c * g)
            bx_spec = P(("lyr", "row"), "col") if c > 1 else P("row", "col")
            args = (distribute(pad_eye(u, m), mesh, P("row", "col")),
                    distribute(_pad_zero(b, mb, m), mesh, bx_spec))
        elif plan.algo == "cholesky":
            (a,) = (jnp.asarray(x) for x in operands)
            m = _round_up(n, g)
            args = (distribute(pad_eye(a, m), mesh, P("row", "col")),)
        else:
            raise ValueError(f"unknown algo {plan.algo!r}")
    with _phase(pt, plan, "execute", algo=plan.algo, variant=plan.variant,
                g=g, c=c, **_diag_args(plan)):
        out = fn(*args)[:n, :n]
        if pt is not None:
            jax.block_until_ready(out)
    if pt is not None:
        pt.emit(store=store, force=observe)
    return out


def _planned(op: str, n: int, operands, devices, tuner, local_kernel,
             observe: bool):
    """Plan ``op`` at size ``n`` and execute the plan, under the
    ``repro.linalg.<op>`` span."""
    t = tuner or default_tuner()
    devs = list(devices) if devices is not None else jax.devices()
    with obs.maybe_span(f"linalg.{op}", cat="dispatch_root", n=n):
        t0 = time.perf_counter()
        with obs.maybe_span("dispatch.plan", cat="dispatch", op=op, n=n):
            plan = t.plan(op, n, devices=devs, dtype=_dtype_key(operands[0]),
                          local_kernel=local_kernel, observe=observe)
        return execute(plan, *operands, devices=devs, observe=observe,
                       store=t.store,
                       _plan_seconds=time.perf_counter() - t0)


def matmul(A, B, *, devices: Optional[Sequence] = None,
           tuner: Optional[Tuner] = None,
           local_kernel: Optional[str] = None,
           observe: bool = False):
    """C = A @ B, model-guided: the tuner races the Cannon and SUMMA models
    over every realizable 2D/2.5D grid and executes the winner."""
    n = _check_square("A", A)
    if tuple(B.shape) != tuple(A.shape):
        raise ValueError(f"A {A.shape} and B {B.shape} must match")
    return _planned("matmul", n, (A, B), devices, tuner, local_kernel,
                    observe)


def trsm(U, B, *, devices: Optional[Sequence] = None,
         tuner: Optional[Tuner] = None,
         local_kernel: Optional[str] = None,
         observe: bool = False):
    """Solve X U = B (U upper-triangular), model-guided."""
    n = _check_square("U", U)
    if tuple(B.shape) != tuple(U.shape):
        raise ValueError(f"U {U.shape} and B {B.shape} must match")
    return _planned("trsm", n, (U, B), devices, tuner, local_kernel, observe)


def cholesky(A, *, devices: Optional[Sequence] = None,
             tuner: Optional[Tuner] = None,
             local_kernel: Optional[str] = None,
             observe: bool = False):
    """L with A = L L^T (A SPD), model-guided."""
    n = _check_square("A", A)
    return _planned("cholesky", n, (A,), devices, tuner, local_kernel,
                    observe)
