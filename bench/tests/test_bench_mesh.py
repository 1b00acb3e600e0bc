"""The four-device linalg cell on four virtual CPU devices (n = 256): the
planned Cannon grid runs correct, and with the exchange between devices
left out the run is not correct.  Each run is a process of its own, since
the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.tiny_root import ROOT, make_root

SCRIPT = """
import json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
if {fault!r} == "exchange_left_out":
    jax.lax.ppermute = lambda x, axis_name, perm: x
    jax.lax.psum = lambda x, axis_name, **kw: x
from repro.tuner import Tuner, PlanCache
import tempfile
plan = Tuner(cache=PlanCache(tempfile.mkdtemp())).plan(
    "matmul", 256, devices=jax.devices()[:4], dtype="float32",
    local_kernel="pallas")
print("PLAN", plan.algo, plan.g, plan.c, flush=True)
from bench import harness
sys.exit(harness.run_cell(["--workload", "tiny.gemm", "--seed", "7",
                           "--seconds", "1", "--trace", "0"],
                          t0=time.perf_counter(), root={root!r},
                          require_tpu=False))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


def run4(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        SCRIPT.format(root=root, fault=fault)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    plan = [ln for ln in p.stdout.splitlines() if ln.startswith("PLAN")][0]
    assert plan.split()[2] == "2", plan      # a 2x2 grid, not one device
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_four_device_gemm(root, fault):
    res = run4(root, fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "none"), res["checks"]
