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

Fitting the model parameters to the paper's *published* tables (the
Hopper surfaces) needs the variant selection above this layer; it lives in
``repro.tuner.paper_fit``.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Sequence

import numpy as np

from .. import compat
from .fitting import multistart_nelder_mead
from .machine import CPU_HOST, Machine
from .perfmodel import ComputeModel, EfficiencyCurve, ROUTINE_FLOPS

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
