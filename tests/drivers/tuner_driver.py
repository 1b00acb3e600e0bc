"""Multi-device tuner-dispatch validation driver (run in a subprocess with
--xla_force_host_platform_device_count=8).  Prints JSON verdicts."""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import linalg  # noqa: E402
from repro.tuner import PlanCache, Tuner, feasible_grids, select  # noqa: E402


def _rel_err(got, ref):
    got, ref = np.asarray(got), np.asarray(ref)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def main():
    out = {}
    rng = np.random.default_rng(0)
    n = 96
    devices = jax.devices()
    plan_dir = tempfile.mkdtemp(prefix="plans-")
    tuner = Tuner(cache=PlanCache(plan_dir))

    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    C_ref = np.asarray(A) @ np.asarray(B)
    U = jnp.asarray(np.triu(rng.standard_normal((n, n))) + 3 * np.eye(n),
                    jnp.float32)
    X_ref = np.asarray(B) @ np.linalg.inv(np.asarray(U))
    M = rng.standard_normal((n, n))
    SPD = jnp.asarray(M @ M.T + n * np.eye(n), jnp.float32)
    L_ref = np.linalg.cholesky(np.asarray(SPD))

    # auto-dispatch numerics (jnp local kernels — the CPU default)
    out["matmul_err"] = _rel_err(linalg.matmul(A, B, tuner=tuner), C_ref)
    out["trsm_err"] = _rel_err(linalg.trsm(U, B, tuner=tuner), X_ref)
    out["cholesky_err"] = _rel_err(linalg.cholesky(SPD, tuner=tuner), L_ref)

    # Pallas local kernels agree with the jnp path
    out["matmul_pallas_err"] = _rel_err(
        linalg.matmul(A, B, tuner=tuner, local_kernel="pallas"), C_ref)
    out["trsm_pallas_err"] = _rel_err(
        linalg.trsm(U, B, tuner=tuner, local_kernel="pallas"), X_ref)
    out["cholesky_pallas_err"] = _rel_err(
        linalg.cholesky(SPD, tuner=tuner, local_kernel="pallas"), L_ref)

    # second identical call is served from the plan cache (no model evals)
    evals = tuner.stats["model_evals"]
    linalg.matmul(A, B, tuner=tuner)
    out["repeat_model_evals_delta"] = tuner.stats["model_evals"] - evals
    out["cache_hits"] = tuner.stats["cache_hits"]

    # ...including from a fresh Tuner (persistent JSON on disk)
    fresh = Tuner(cache=PlanCache(plan_dir))
    fresh.cache.clear_memory()
    fresh.plan("matmul", n, devices=devices)
    out["fresh_tuner_model_evals"] = fresh.stats["model_evals"]
    out["fresh_tuner_disk_hits"] = fresh.cache.disk_hits

    # the dispatched variant equals select.select over the same
    # realizable configurations
    plan = tuner.plan("matmul", n, devices=devices)
    ctx = tuner.registry.context(plan.machine)
    best = None
    for algo in ("cannon", "summa"):
        for p, c, g in feasible_grids(len(devices), algo):
            kind = "2d" if c == 1 else "2.5d"
            variants = [v for v in tuner.registry.variants(algo)
                        if v.startswith(kind)]
            ch = select.select(ctx, algo, n, p, variants=variants,
                               c_values=[c], r_values=(1,))
            if best is None or ch.result.total < best[0].result.total:
                best = (ch, algo)
    out["plan_matches_select"] = bool(best[1] == plan.algo and
                                      best[0].result.variant == plan.variant)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
