"""The paper's algorithms, executable: run 2D vs 2.5D Cannon / TRSM /
Cholesky on forced host devices and check them against numpy — then ask
the performance model which variant a Cray XE6 or a TPU pod should use.

    python examples/linalg_25d_demo.py          (sets its own XLA_FLAGS)
"""

import os
import sys

if "--xla-set" not in sys.argv:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.linalg import (cannon_25d, cannon_2d, cholesky_25d, distribute,
                          trsm_25d)  # noqa: E402
from repro.linalg.grid import make_grid_mesh  # noqa: E402


def main():
    n = 64
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)

    mesh2 = make_grid_mesh(2, 2)
    mesh3 = make_grid_mesh(2, 2, layers=2)
    C2 = np.asarray(cannon_2d(distribute(A, mesh2), distribute(B, mesh2),
                              mesh=mesh2))
    C25 = np.asarray(cannon_25d(distribute(A, mesh3, P("row", "col")),
                                distribute(B, mesh3, P("row", "col")),
                                mesh=mesh3))
    ref = np.asarray(A) @ np.asarray(B)
    print(f"cannon 2D err {np.abs(C2-ref).max():.2e} | "
          f"2.5D (c=2) err {np.abs(C25-ref).max():.2e}")

    U = jnp.asarray(np.triu(rng.standard_normal((n, n))) + 3 * np.eye(n),
                    jnp.float32)
    X = np.asarray(trsm_25d(distribute(U, mesh3, P("row", "col")),
                            distribute(B, mesh3, P(("lyr", "row"), "col")),
                            mesh=mesh3))
    print(f"trsm 2.5D err {np.abs(X @ np.asarray(U) - np.asarray(B)).max():.2e}")

    SPD = jnp.asarray(np.asarray(A) @ np.asarray(A).T + n * np.eye(n),
                      jnp.float32)
    L = np.asarray(cholesky_25d(distribute(SPD, mesh3, P("row", "col")),
                                mesh=mesh3))
    print(f"cholesky 2.5D err {np.abs(L @ L.T - np.asarray(SPD)).max():.2e}")

    # model-guided dispatch: the tuner picks variant + grid + kernels,
    # executes, and caches the plan under artifacts/plans/
    from repro import linalg
    from repro.tuner import default_tuner
    C = np.asarray(linalg.matmul(A, B))
    plan = default_tuner().plan("matmul", n)
    print(f"\ntuner dispatch: {plan.algo}/{plan.variant} p={plan.p} "
          f"c={plan.c} kernel={plan.local_kernel} "
          f"err {np.abs(C-ref).max():.2e} "
          f"(predicted {plan.predicted['total']*1e3:.2f} ms)")

    # and the model's advice for real machines
    from repro.core import AlgoContext, CommModel, ComputeModel, TPU_V5E
    from repro.core.perfmodel import TPU_EFFICIENCY
    from repro.tuner.paper_fit import hopper_fitted_ctx
    from repro.tuner.select import select
    from repro.sim import derive_calibration, v5e_pod_topology
    ctx_h = hopper_fitted_ctx()
    ch = select(ctx_h, "cholesky", 65536, 4096)
    print(f"\nHopper @24k cores, cholesky n=65536 -> "
          f"{ch.result.variant} (c={ch.result.c}, {ch.pct_peak:.1f}% peak)")
    cal = derive_calibration(v5e_pod_topology(), ps=[64, 256],
                             distances=[1, 4, 16])
    ctx_t = AlgoContext(CommModel(TPU_V5E, cal),
                        ComputeModel(TPU_V5E, TPU_EFFICIENCY))
    ch = select(ctx_t, "cholesky", 131072, 256)
    print(f"v5e pod (256 chips), cholesky n=131072 -> "
          f"{ch.result.variant} (c={ch.result.c}, {ch.pct_peak:.1f}% peak)")


if __name__ == "__main__":
    main()
