"""Paper Figs. 5-8 analog: model estimates vs *measured* execution of the
executable algorithms — on the only machine physically present (host CPU
devices).  This is the live end-to-end validation of the methodology:

  1. benchmark the machine (Fig. 1/2/3-4 ingredients) -> model parameters;
  2. run each algorithm variant, measure wall time;
  3. compare est_Cal vs est_NoCal (paper's punchline: the calibration
     factor is what makes estimates rank variants correctly).

Host-device caveat (documented in EXPERIMENTS.md): all p "devices" share
one physical core, so per-unit peak is measured_core_peak / p and the
"network" is shared memcpy — the methodology is what's validated, not TPU
numbers.
"""

import dataclasses
import json
import time


def _measure(fn, *args, reps=3):
    import jax
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import AlgoContext, CommModel, CalibrationTable
    from repro.perf import EvalOptions
    from repro.tuner import DEFAULT_REGISTRY
    evaluate = DEFAULT_REGISTRY.evaluate
    from repro.core.calibration import (bench_ping, fit_alpha_beta,
                                        measured_compute_model)
    from repro.linalg import ALGORITHMS, distribute
    from repro.linalg.grid import make_grid_mesh

    n_dev = len(jax.devices())
    g = int(np.sqrt(n_dev))  # 2D grid g x g
    p2d = g * g

    # --- 1. machine parameters (the portable benchmarks) -------------------
    comp = measured_compute_model(sizes=(128, 256, 512))
    comp = dataclasses.replace(
        comp, machine=dataclasses.replace(
            comp.machine,
            peak_flops_per_unit=comp.machine.peak_flops_per_unit / p2d,
            threads_per_unit=1))
    # include small messages so the latency intercept is identifiable
    ping = bench_ping(sizes_words=(64, 1 << 10, 1 << 14, 1 << 18, 1 << 21),
                      reps=7)
    L, beta = fit_alpha_beta(ping)
    machine = dataclasses.replace(comp.machine, latency=L, inv_bandwidth=beta)
    comp = dataclasses.replace(comp, machine=machine)

    # contention: measured factor at two distances -> small table
    from repro.core.calibration import bench_contention
    words = 1 << 19
    ideal = L + beta * words
    avg, mx = {}, {}
    for d in (1, max(2, g)):
        wall = bench_contention(p2d, d, words=words)
        avg[float(d)] = max(1.0, wall / ideal)
        mx[(float(p2d), float(d))] = max(1.0, wall / ideal)
    cal = CalibrationTable(avg=avg, mx=mx, extrapolation_degree=1)
    # One context; est_Cal vs est_NoCal are evaluation options, not
    # rebuilt calibration surfaces.
    ctx_cal = AlgoContext(CommModel(machine, cal), comp)

    # --- 2. run + 3. compare ------------------------------------------------
    # block size must be large enough that compute amortizes dispatch
    n = 512 * g
    rng = np.random.default_rng(0)
    mesh = make_grid_mesh(g, g)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    U = jnp.asarray(np.triu(rng.standard_normal((n, n))) + 3 * np.eye(n),
                    jnp.float32)
    SPD = jnp.asarray(np.asarray(A) @ np.asarray(A).T + n * np.eye(n),
                      jnp.float32)
    Ad, Bd = distribute(A, mesh), distribute(B, mesh)
    Ud, Sd = distribute(U, mesh), distribute(SPD, mesh)

    results = {}
    for (algo, variant), fn in ALGORITHMS.items():
        if variant.startswith("2.5d"):
            continue  # 2D grid here; 2.5D measured in the multi-layer bench
        if algo in ("cannon", "summa"):
            meas = _measure(lambda: fn(Ad, Bd, mesh=mesh))
        elif algo == "trsm":
            meas = _measure(lambda: fn(Ud, Bd, mesh=mesh))
        else:
            meas = _measure(lambda: fn(Sd, mesh=mesh))
        est_c = evaluate(ctx_cal, algo, variant, n, p2d, r=1).total
        est_n = evaluate(ctx_cal, algo, variant, n, p2d, r=1,
                         options=EvalOptions("nocal")).total
        results[f"{algo}_{variant}"] = {
            "measured_s": meas, "est_cal_s": est_c, "est_nocal_s": est_n,
            "cal_rel_err": abs(est_c - meas) / meas,
            "nocal_rel_err": abs(est_n - meas) / meas,
        }

    cal_errs = [v["cal_rel_err"] for v in results.values()]
    nocal_errs = [v["nocal_rel_err"] for v in results.values()]
    return {"n": n, "p": p2d, "machine_peak_per_unit": machine.peak_flops_per_unit,
            "latency_s": L, "beta": beta,
            "measured_factors": {str(k): v for k, v in avg.items()},
            "results": results,
            "geomean_rel_err_cal": float(np.exp(np.mean(np.log(np.maximum(cal_errs, 1e-9))))),
            "geomean_rel_err_nocal": float(np.exp(np.mean(np.log(np.maximum(nocal_errs, 1e-9)))))}


if __name__ == "__main__":
    print(json.dumps(main()))
