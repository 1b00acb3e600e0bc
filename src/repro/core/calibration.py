"""Portable benchmarks + fitting for the model parameters (paper §IV).

Three benchmark families, exactly mirroring the paper:

1. ``bench_routines`` — local-routine efficiency (paper Fig. 1): times each
   BLAS-like routine over block sizes on one device and fits an
   ``EfficiencyCurve`` per routine.
2. ``bench_ping`` — the LogP latency/bandwidth benchmark (paper Fig. 2):
   two devices exchange messages of increasing size; (L, beta) by least
   squares.
3. ``bench_contention`` — the paper's new calibration micro-benchmark
   (Figs. 3-4): all p processes transfer simultaneously at communication
   distance d; the calibration factor is real/ideal time.

All three run on whatever devices JAX exposes (here: host CPU devices; on a
real pod: TPU chips) — the benchmarks are the portable part of the
methodology, the numbers are machine-specific.

Because a single-process CPU run cannot observe *per-rank* completion times
(everything is jitted SPMD), the repo derives deterministic
``C_avg``/``C_max`` surfaces from a dimension-ordered-routing link-load
model of a torus.  That model lives in ``repro.sim`` (topologies, the
link-contention network engine, and the full per-rank program simulator);
see ``repro.sim.derive_calibration``.

``fit_hopper_calibration`` recovers the paper's (unpublished) calibration
surface by fitting ``ParametricCalibration`` to the paper's *published*
Cannon predictions (Table II) — then §Paper-validation checks the fit
transfers to SUMMA/TRSM/Cholesky (Tables III-V), which is the paper's own
claim that one set of benchmarked parameters predicts all algorithms.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Dict, Optional, Sequence

import numpy as np

from . import algorithms as alg
from .. import compat
from .fitting import multistart_nelder_mead
from .machine import CPU_HOST, HOPPER, Machine
from .paper_data import CORE_COUNTS, PAPER_TABLES
from .perfmodel import (CommModel, ComputeModel, EfficiencyCurve,
                        HOPPER_EFFICIENCY, ParametricCalibration,
                        ROUTINE_FLOPS)

ARTIFACTS_DIR = os.environ.get(
    "REPRO_ARTIFACTS", os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts"))


# ---------------------------------------------------------------------------
# 1. Local routine efficiency (paper Fig. 1)
# ---------------------------------------------------------------------------


def _time_call(fn, *args, reps: int = 3) -> float:
    fn(*args)  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        _block(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _block(x):
    try:
        import jax
        jax.block_until_ready(x)
    except Exception:
        pass


def bench_routines(sizes: Sequence[int] = (128, 256, 512, 1024, 2048),
                   dtype=None) -> Dict[str, Dict[int, float]]:
    """Measured GFLOP/s of each routine per block size (Fig. 1 analog)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float64
    results: Dict[str, Dict[int, float]] = {r: {} for r in ROUTINE_FLOPS}
    key = jax.random.PRNGKey(0)
    for n in sizes:
        a = jax.random.normal(key, (n, n), dtype=jnp.float32).astype(dtype)
        spd = (a @ a.T + n * jnp.eye(n, dtype=dtype))
        tri = jnp.triu(a) + n * jnp.eye(n, dtype=dtype)
        fns = {
            "dgemm": jax.jit(lambda x, y: x @ y),
            "dtrsm": jax.jit(lambda u, b: jax.scipy.linalg.solve_triangular(u, b, lower=False)),
            "dsyrk": jax.jit(lambda x, y: x @ y.T),
            "dpotrf": jax.jit(jnp.linalg.cholesky),
            "dgetrf": jax.jit(jax.scipy.linalg.lu),
        }
        args = {"dgemm": (a, a), "dtrsm": (tri, a), "dsyrk": (a, a),
                "dpotrf": (spd,), "dgetrf": (spd,)}
        for rout in ROUTINE_FLOPS:
            secs = _time_call(fns[rout], *args[rout])
            results[rout][n] = ROUTINE_FLOPS[rout](n) / secs
    return results


def fit_efficiency(gflops_by_size: Dict[int, float], peak: float) -> EfficiencyCurve:
    sizes = np.array(sorted(gflops_by_size))
    effs = np.array([gflops_by_size[int(n)] / peak for n in sizes])
    effs = np.clip(effs, 1e-4, 1.0)

    def loss(theta):
        emax, n0 = abs(theta[0]), abs(theta[1]) + 1.0
        pred = np.clip(emax * (1 - np.exp(-sizes / n0)), 1e-4, None)
        return float(np.mean((np.log(pred) - np.log(effs)) ** 2))

    theta, _ = multistart_nelder_mead(loss, np.array([effs.max(), 300.0]), n_starts=4)
    return EfficiencyCurve(float(abs(theta[0])), float(abs(theta[1]) + 1.0))


def measured_compute_model(machine: Machine = CPU_HOST,
                           sizes: Sequence[int] = (128, 256, 512, 1024)) -> ComputeModel:
    """Benchmark this host and return a fitted ComputeModel.  Also updates
    the machine's peak to the best observed dgemm rate (the paper uses the
    vendor peak; on an unknown host, measured peak is the honest analog)."""
    bench = bench_routines(sizes)
    peak = max(bench["dgemm"].values())
    machine = dataclasses.replace(machine, peak_flops_per_unit=peak)
    curves = {r: fit_efficiency(v, peak) for r, v in bench.items()}
    return ComputeModel(machine, curves)


# ---------------------------------------------------------------------------
# 2. LogP ping (paper Fig. 2): fit L and beta
# ---------------------------------------------------------------------------


def bench_ping(sizes_words: Sequence[int] = (256, 1024, 4096, 16384, 65536, 262144),
               word_bytes: int = 8, reps: int = 5) -> Dict[int, float]:
    """Round-trip/2 time between two JAX devices per message size (words)."""
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError("bench_ping needs >= 2 devices "
                           "(set --xla_force_host_platform_device_count)")
    dtype = jnp.float64 if word_bytes == 8 else jnp.float32
    out: Dict[int, float] = {}
    for w in sizes_words:
        x = jnp.ones((w,), dtype)
        xa = jax.device_put(x, devs[0])
        def ping(y):
            return jax.device_put(y, devs[1])
        ping(xa)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(ping(xa))
            best = min(best, time.perf_counter() - t0)
        out[w] = best
    return out


def fit_alpha_beta(ping: Dict[int, float]) -> tuple[float, float]:
    """Least-squares (L, beta) from T(w) = L + beta*w."""
    ws = np.array(sorted(ping))
    ts = np.array([ping[int(w)] for w in ws])
    A = np.stack([np.ones_like(ws, dtype=float), ws.astype(float)], axis=1)
    (L, beta), *_ = np.linalg.lstsq(A, ts, rcond=None)
    return float(max(L, 1e-9)), float(max(beta, 1e-15))


# ---------------------------------------------------------------------------
# 3. Contention calibration benchmark (paper Figs. 3-4)
# ---------------------------------------------------------------------------


def bench_contention(n_procs: int, distance: int, words: int = 1 << 20,
                     word_bytes: int = 8, reps: int = 5) -> float:
    """All n_procs devices ppermute by ``distance`` simultaneously; returns
    wall seconds of the slowest (i.e., the C_max-style observation — in an
    SPMD jit there is a single completion time)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()[:n_procs]
    if len(devs) < n_procs:
        raise RuntimeError(f"need {n_procs} devices, have {len(devs)}")
    mesh = compat.make_mesh((n_procs,), ("x",), devices=devs)
    dtype = jnp.float64 if word_bytes == 8 else jnp.float32

    def shift(x):
        perm = [(i, (i + distance) % n_procs) for i in range(n_procs)]
        return jax.lax.ppermute(x, "x", perm)

    run = jax.jit(jax.shard_map(shift, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x")))

    x = jnp.ones((n_procs * words,), dtype)
    xs = jax.device_put(x, NamedSharding(mesh, P("x")))
    run(xs)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(xs))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# Fit the Hopper calibration surface to the paper's published Table II
# ---------------------------------------------------------------------------


def _hopper_ctx(calib: ParametricCalibration) -> alg.AlgoContext:
    return alg.AlgoContext(
        comm=CommModel(HOPPER, calib),
        comp=ComputeModel(HOPPER, HOPPER_EFFICIENCY),
    )


def _table_residuals(calib: ParametricCalibration, algos: Sequence[str]) -> np.ndarray:
    """log-space residuals of our models vs the paper's published tables."""
    from .predictor import best_variant
    ctx = _hopper_ctx(calib)
    res = []
    for algo in algos:
        for size, rows in PAPER_TABLES[algo].items():
            for ci, cores in enumerate(CORE_COUNTS):
                p = cores // HOPPER.threads_per_unit
                choices = best_variant(ctx, algo, size, p)
                for variant, published in rows.items():
                    pred = choices[variant]
                    pred_pct = (100.0 * alg.USEFUL_FLOPS[algo](size)
                                / (pred.result.total * cores * HOPPER.peak_flops_per_thread))
                    res.append(math.log(max(pred_pct, 1e-6)) - math.log(published[ci]))
    return np.array(res)


def fit_hopper_calibration(fit_algos: Sequence[str] = ("cannon",),
                           n_starts: int = 6, seed: int = 0) -> ParametricCalibration:
    def loss(theta):
        calib = ParametricCalibration.from_params(theta)
        r = _table_residuals(calib, fit_algos)
        return float(np.mean(r ** 2))

    x0 = ParametricCalibration().params()
    theta, _ = multistart_nelder_mead(loss, x0, n_starts=n_starts, seed=seed,
                                      max_iter=400)
    return ParametricCalibration.from_params(np.abs(theta))


def hopper_fitted_calibration(refit: bool = False) -> ParametricCalibration:
    """Cached fitted surface (artifacts/hopper_calibration.json)."""
    os.makedirs(ARTIFACTS_DIR, exist_ok=True)
    path = os.path.join(ARTIFACTS_DIR, "hopper_calibration.json")
    if not refit and os.path.exists(path):
        with open(path) as f:
            return ParametricCalibration.from_params(json.load(f)["params"])
    calib = fit_hopper_calibration()
    with open(path, "w") as f:
        json.dump({"params": [float(x) for x in calib.params()]}, f)
    return calib


def _ctx_from_theta(theta: np.ndarray) -> alg.AlgoContext:
    """theta = 5 calibration params + (eff_max, n0) for dgemm/dtrsm/dpotrf.
    dsyrk tracks dgemm (same MXU/BLAS3 path).  Efficiency parameters are
    box-constrained to the visually-plausible range of paper Fig. 1
    (eff_max in [0.5, 0.98], n0 in [80, 1200]) so the fit can't push
    compute curves into absurd regions to absorb model-structure error."""

    def _eff(em, n0):
        return EfficiencyCurve(float(np.clip(abs(em), 0.5, 0.98)),
                               float(np.clip(abs(n0), 80.0, 1200.0)))

    calib = ParametricCalibration.from_params(np.abs(theta[:5]))
    eff = {
        "dgemm": _eff(theta[5], theta[6]),
        "dtrsm": _eff(theta[7], theta[8]),
        "dpotrf": _eff(theta[9], theta[10]),
        "dsyrk": _eff(theta[5], theta[6]),
    }
    return alg.AlgoContext(comm=CommModel(HOPPER, calib),
                           comp=ComputeModel(HOPPER, eff))


def _residuals_ctx(ctx: alg.AlgoContext, algos: Sequence[str],
                   core_idx: Optional[Sequence[int]] = None) -> np.ndarray:
    from .predictor import best_variant
    res = []
    for algo in algos:
        for size, rows in PAPER_TABLES[algo].items():
            for ci, cores in enumerate(CORE_COUNTS):
                if core_idx is not None and ci not in core_idx:
                    continue
                p = cores // HOPPER.threads_per_unit
                choices = best_variant(ctx, algo, size, p)
                for variant, published in rows.items():
                    pred = choices[variant]
                    pred_pct = (100.0 * alg.USEFUL_FLOPS[algo](size)
                                / (pred.result.total * cores * HOPPER.peak_flops_per_thread))
                    res.append(math.log(max(pred_pct, 1e-6)) - math.log(published[ci]))
    return np.array(res)


def fit_hopper_joint(train_core_idx: Sequence[int] = (0, 2, 4),
                     n_starts: int = 4, seed: int = 0) -> tuple[alg.AlgoContext, np.ndarray]:
    """Jointly fit calibration + routine-efficiency curves on a train split
    (alternate core counts, all four tables); returns (ctx, theta).
    Held-out columns {1, 3} are the validation set."""

    def loss(theta):
        ctx = _ctx_from_theta(theta)
        r = _residuals_ctx(ctx, list(PAPER_TABLES), core_idx=train_core_idx)
        return float(np.mean(r ** 2))

    x0 = np.concatenate([ParametricCalibration().params(),
                         [0.92, 350.0, 0.85, 500.0, 0.70, 600.0]])
    theta, _ = multistart_nelder_mead(loss, x0, n_starts=n_starts, seed=seed,
                                      max_iter=600)
    return _ctx_from_theta(theta), theta


def fit_hopper_two_stage(train_core_idx: Sequence[int] = (0, 2, 4),
                         n_starts: int = 6, seed: int = 0) -> tuple[alg.AlgoContext, np.ndarray]:
    """Two-stage fit mirroring the paper's measurement independence:

    stage 1 — calibration surface + dgemm curve from the pure-dgemm
              algorithms (Cannon + SUMMA);
    stage 2 — dtrsm / dpotrf curves from TRSM + Cholesky with stage-1
              parameters frozen (they only add routine terms).
    """

    def loss1(sub):
        theta = np.concatenate([sub, [0.85, 500.0, 0.70, 600.0]])
        ctx = _ctx_from_theta(theta)
        r = _residuals_ctx(ctx, ["cannon", "summa"], core_idx=train_core_idx)
        return float(np.mean(r ** 2))

    x0 = np.concatenate([ParametricCalibration().params(), [0.92, 350.0]])
    sub1, _ = multistart_nelder_mead(loss1, x0, n_starts=n_starts, seed=seed,
                                     max_iter=800)

    def loss2(sub):
        theta = np.concatenate([sub1, sub])
        ctx = _ctx_from_theta(theta)
        r = _residuals_ctx(ctx, ["trsm", "cholesky"], core_idx=train_core_idx)
        return float(np.mean(r ** 2))

    sub2, _ = multistart_nelder_mead(loss2, np.array([0.85, 500.0, 0.70, 600.0]),
                                     n_starts=n_starts, seed=seed, max_iter=800)
    theta = np.concatenate([sub1, sub2])
    return _ctx_from_theta(theta), theta


def hopper_fitted_ctx(refit: bool = False) -> alg.AlgoContext:
    """Cached jointly-fitted Hopper context (artifacts/hopper_joint.json)."""
    os.makedirs(ARTIFACTS_DIR, exist_ok=True)
    path = os.path.join(ARTIFACTS_DIR, "hopper_joint.json")
    if not refit and os.path.exists(path):
        with open(path) as f:
            theta = np.array(json.load(f)["theta"])
        return _ctx_from_theta(theta)
    ctx, theta = fit_hopper_two_stage()
    with open(path, "w") as f:
        json.dump({"theta": [float(x) for x in theta]}, f)
    return ctx


def joint_validation_report(ctx: alg.AlgoContext,
                            held_out_idx: Sequence[int] = (1, 3)) -> Dict[str, Dict[str, float]]:
    """Per-table geo-mean relative error and max absolute %-of-peak error
    (the paper's own accuracy metric) on the held-out core counts."""
    from .predictor import best_variant
    out: Dict[str, Dict[str, float]] = {}
    for algo in PAPER_TABLES:
        rel = _residuals_ctx(ctx, [algo], core_idx=held_out_idx)
        abs_err = []
        for size, rows in PAPER_TABLES[algo].items():
            for ci, cores in enumerate(CORE_COUNTS):
                if ci not in held_out_idx:
                    continue
                p = cores // HOPPER.threads_per_unit
                choices = best_variant(ctx, algo, size, p)
                for variant, published in rows.items():
                    pred_pct = (100.0 * alg.USEFUL_FLOPS[algo](size)
                                / (choices[variant].result.total * cores
                                   * HOPPER.peak_flops_per_thread))
                    abs_err.append(abs(pred_pct - published[ci]))
        out[algo] = {
            "geo_mean_rel_err": float(np.exp(np.sqrt(np.mean(rel ** 2))) - 1.0),
            "max_abs_pct_points": float(np.max(abs_err)),
            "mean_abs_pct_points": float(np.mean(abs_err)),
        }
    return out


def validation_report(calib: ParametricCalibration) -> Dict[str, float]:
    """Geometric-mean relative error of our fitted models vs each published
    table (fit quality on cannon; *transfer* quality on the rest)."""
    out = {}
    for algo in PAPER_TABLES:
        r = _table_residuals(calib, [algo])
        out[algo] = float(np.exp(np.sqrt(np.mean(r ** 2))) - 1.0)
    return out
