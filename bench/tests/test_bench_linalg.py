"""The linalg cells on the CPU at n = 256: a whole run is correct, its
reference agrees with numpy, its control is not correct, and every fault
planted under the timed path makes ``correct`` false."""

import numpy as np
import pytest

from bench import control, harness
from bench.tests.tiny_root import BENCH, jax_state, make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


def test_tiny_mix_runs_correct(root):
    rc, res, err = run(root, "tiny.mix")
    assert rc == 0 and res["correct"], err
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert set(res["metrics"]) == {"linalg_tflops", "setup_s"}
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"matmul.residual", "trsm.residual",
                                  "cholesky.residual", "cholesky.upper"}
    assert res["window"]["compiled_in_window"] == []
    assert err.strip().splitlines()[-1].startswith("check ")


def test_tiny_mix_traced_run_reports_layer_metrics(root, monkeypatch):
    from bench import trace
    from bench.tests.tiny_root import cpu_trace_load
    monkeypatch.setattr(trace, "load", cpu_trace_load)
    rc, res, err = run(root, "tiny.mix", trace=1)
    assert rc == 0 and res["correct"], err
    assert {"linalg_mfu", "planner.model_err",
            "device_idle.linalg"} <= set(res["metrics"])
    assert "linalg_tflops" not in res["metrics"]
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_reference_agrees_with_numpy():
    import jax
    ref = harness.load_module(BENCH + "/configs/linalg_f32_ref.py")
    ops = ref.operands(harness.seed_key(9), 128, ("a", "b", "u", "s"))
    a, b, u, s = (np.asarray(ops[k], np.float64) for k in "abus")
    np.testing.assert_allclose(ref.compute("matmul", ops), a @ b, rtol=0,
                               atol=1e-4)
    x = np.asarray(ref.compute("trsm", ops))
    np.testing.assert_allclose(x @ u, b, atol=1e-4)
    l_ = np.asarray(ref.compute("cholesky", ops))
    np.testing.assert_allclose(l_, np.linalg.cholesky(s), atol=1e-5)
    assert np.abs(np.triu(l_, 1)).max() == 0
    # bf16_3x is the MXU's three-pass product: close, but not float32
    lo = np.asarray(ref.dot(ops["a"], ops["b"], "bf16_3x"))
    err = np.abs(lo - a @ b).max()
    assert 1e-6 < err < 1e-2
    del jax


def test_control_is_not_correct(root):
    """The reference in bf16_3x in the program's place fails every
    residual limit of the cell; the program passes them (n = 256, two
    seeds)."""
    limits = harness.load_json(BENCH + "/traffic/mix.n16384.json")["limits"]
    with jax_state():
        rows = control.readings(["--workload", "tiny.mix", "--seeds", "5",
                                 "2147483653"], root=root, require_tpu=False,
                                out=open("/dev/null", "w"))
    for row in rows:
        assert all(row[k] <= v for k, v in limits.items()), row
        for k, v in limits.items():
            if k.endswith(".residual"):
                assert row["control." + k] > v, (k, row)


def test_control_run_comes_out_not_correct(root):
    """A run with the bf16_3x reference in the program's place fails every
    residual limit through the harness's own comparison."""
    rc, res, err = run(root, "tiny.mix", control=1)
    assert rc == 0 and res["correct"] is False, err
    assert res["failed"] == res["attempted"] > 0
    for name, c in res["checks"].items():
        if name.endswith(".residual"):
            assert c["value"] > c["limit"], (name, c)


def _patched_execute(monkeypatch, fault):
    from repro.tuner import dispatch
    orig = dispatch.execute

    def execute(plan, *operands, **kw):
        return fault(orig(plan, *operands, **kw), operands)
    monkeypatch.setattr(dispatch, "execute", execute)


FAULTS = {
    # one entry of the answer altered where it is produced
    "answer_altered": lambda out, ops: out.at[17, 33].add(1.0),
    # half of the rows of the answer left out
    "half_left_out": lambda out, ops: out.at[out.shape[0] // 2:].set(0.0),
    # the call returns its input unchanged
    "state_unchanged": lambda out, ops: ops[-1],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_not_correct(root, monkeypatch, fault):
    _patched_execute(monkeypatch, FAULTS[fault])
    rc, res, err = run(root, "tiny.mix")
    assert rc == 0 and res is not None
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0
