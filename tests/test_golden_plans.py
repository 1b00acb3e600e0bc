"""Golden plans: the planner's decisions for the benchmark's scenarios.

``tests/golden/plans.json`` snapshots ``Tuner.plan(...).to_dict()`` for
matmul, TRSM and Cholesky at n in {4096, 16384} on one and four TPU v5e
chips.  A refactor of the planning layer (registry, selection, cost-IR
evaluation) must leave every plan unchanged field for field; a deliberate
model change regenerates the file with ``python tests/test_golden_plans.py``.

Also pins the layering: ``import repro.core`` loads neither the cost-IR
(``repro.perf``), the tuner nor the simulator.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.tuner import PlanCache, Tuner

HERE = os.path.dirname(__file__)
GOLD_PATH = os.path.join(HERE, "golden", "plans.json")
EXACT = ("algo", "variant", "g", "c", "r", "tiles", "local_kernel",
         "fingerprint")
REL_TOL = 1e-12
SCENARIOS = [(op, n, d) for op in ("matmul", "trsm", "cholesky")
             for n in (4096, 16384) for d in (1, 4)]


def _plan(plan_dir: str, op: str, n: int, d: int) -> dict:
    tuner = Tuner(cache=PlanCache(plan_dir))
    return tuner.plan(op, n, device_count=d, platform="tpu",
                      device_kind="TPU v5 lite", dtype="float32",
                      use_cache=False).to_dict()


def _key(op: str, n: int, d: int) -> str:
    return f"{op}/n{n}/d{d}"


def _load() -> dict:
    with open(GOLD_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("op,n,d", SCENARIOS)
def test_plan_matches_golden(tmp_path, op, n, d):
    want = _load()[_key(op, n, d)]
    got = _plan(str(tmp_path), op, n, d)
    for field in EXACT:
        assert got[field] == want[field], field
    assert set(got["predicted"]) == set(want["predicted"])
    for name, value in want["predicted"].items():
        assert got["predicted"][name] == pytest.approx(value, rel=REL_TOL), \
            name


def test_import_core_loads_no_upper_layer():
    code = ("import sys, repro.core; "
            "print(sorted(m for m in sys.modules if m.split('.')[:2] in "
            "(['repro', 'perf'], ['repro', 'tuner'], ['repro', 'sim'])))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(HERE, "..", "src")}
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, env=env)
    assert out.stdout.strip() == "[]", out.stdout


if __name__ == "__main__":
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        gold = {_key(*s): _plan(tmp, *s) for s in SCENARIOS}
    with open(GOLD_PATH, "w") as f:
        json.dump(gold, f, indent=1, sort_keys=True)
        f.write("\n")
