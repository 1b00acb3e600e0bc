"""Tests for the repro.tuner subsystem: registry queries, plan-cache
persistence/invalidation, feasible-grid enumeration, and (in a subprocess
with 8 forced host devices) end-to-end model-guided dispatch numerics."""

import json
import os
import subprocess
import sys

import pytest

from repro.core import predictor
from repro.core.machine import CPU_HOST, HOPPER
from repro.tuner import (DEFAULT_REGISTRY, ExecutionPlan, PerfModelRegistry,
                         PlanCache, Tuner, feasible_grids, machine_fingerprint,
                         machine_for_platform, plan_key)

HERE = os.path.dirname(__file__)


class TestRegistry:
    def test_unifies_algorithm_models(self):
        from repro.core.algorithms import MODELS
        assert set(DEFAULT_REGISTRY.algos()) == {a for a, _ in MODELS}
        for algo in DEFAULT_REGISTRY.algos():
            assert set(DEFAULT_REGISTRY.variants(algo)) == \
                {v for a, v in MODELS if a == algo}

    def test_evaluate_matches_core(self):
        from repro.core.algorithms import evaluate
        ctx = DEFAULT_REGISTRY.context(HOPPER.name)
        r1 = DEFAULT_REGISTRY.evaluate(ctx, "cannon", "2.5d", 32768, 1024, c=4)
        r2 = evaluate(ctx, "cannon", "2.5d", 32768, 1024, c=4)
        assert r1.total == r2.total

    def test_duplicate_registration_raises(self):
        reg = PerfModelRegistry()
        reg.register_algorithm("x", "2d", lambda *a, **k: None)
        with pytest.raises(ValueError):
            reg.register_algorithm("x", "2d", lambda *a, **k: None)

    def test_unknown_keys_raise_helpfully(self):
        with pytest.raises(KeyError, match="registered"):
            DEFAULT_REGISTRY.model("cannon", "3d")
        with pytest.raises(KeyError, match="registered"):
            DEFAULT_REGISTRY.machine("cray-ymp")

    def test_collectives_registered(self):
        assert "t_bcast" in DEFAULT_REGISTRY.collectives()
        from repro.core import collectives
        assert DEFAULT_REGISTRY.collective("t_bcast") is collectives.t_bcast

    def test_machine_for_platform(self):
        assert machine_for_platform("cpu") == CPU_HOST.name
        assert machine_for_platform("tpu", "TPU v5 lite") == "tpu-v5e"
        # a device without a profile is an error, never a default
        with pytest.raises(ValueError, match="no machine profile"):
            machine_for_platform("tpu", "TPU v4")
        with pytest.raises(ValueError, match="no machine profile"):
            machine_for_platform("rocm")


class TestLegalCValues:
    def test_no_silent_fallback(self):
        # p=2 (cap < 2) and p=6 (p/c never square) have no legal factor
        assert predictor.legal_c_values(2) == []
        assert predictor.legal_c_values(6) == []

    def test_legal_factors_are_legal(self):
        import math
        for p in (64, 256, 1024, 4096):
            for c in predictor.legal_c_values(p):
                g = math.sqrt(p / c)
                assert abs(g - round(g)) < 1e-9


class TestFeasibleGrids:
    def test_grids_are_realizable(self):
        for d in (1, 4, 8, 9, 16, 64, 256):
            for algo in ("cannon", "summa", "trsm", "cholesky"):
                for p, c, g in feasible_grids(d, algo):
                    assert p == c * g * g <= d
                    assert c <= g or c == 1
                    if c > 1 and algo in ("cannon", "summa"):
                        assert g % c == 0

    def test_always_offers_2d(self):
        for d in (1, 2, 3, 8):
            grids = feasible_grids(d, "cannon")
            assert any(c == 1 for _, c, _ in grids)


class TestPlanning:
    def test_variant_matches_predictor_select(self, tmp_path):
        # 4 devices: the only realizable grid is 2x2 (p=4, c=1), so the
        # dispatcher's choice must equal predictor.select over 2D variants.
        t = Tuner(cache=PlanCache(str(tmp_path)))
        for algo in ("cholesky", "trsm", "summa"):
            plan = t.plan(algo, 8192, device_count=4, platform="cpu",
                          device_kind="test-cpu")
            ctx = t.registry.context("cpu-host")
            ch = predictor.select(ctx, algo, 8192, 4,
                                  variants=("2d", "2d_ovlp"), r_values=(1,))
            assert plan.p == 4 and plan.c == 1
            assert plan.variant == ch.result.variant

    def test_plan_cache_roundtrip_and_persistence(self, tmp_path):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        plan = t.plan("matmul", 4096, device_count=8, platform="cpu",
                      device_kind="test-cpu")
        assert t.stats == {"model_evals": 1, "cache_hits": 0}
        # JSON round-trip through the on-disk payload
        files = os.listdir(tmp_path)
        assert len(files) == 1 and files[0].endswith(".json")
        with open(tmp_path / files[0]) as f:
            restored = ExecutionPlan.from_dict(json.load(f))
        assert restored == plan

        # same scenario, same Tuner: memory hit
        again = t.plan("matmul", 4096, device_count=8, platform="cpu",
                       device_kind="test-cpu")
        assert again == plan
        assert t.stats == {"model_evals": 1, "cache_hits": 1}

        # fresh Tuner over the same directory: disk hit, no model eval
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        got = t2.plan("matmul", 4096, device_count=8, platform="cpu",
                      device_kind="test-cpu")
        assert got == plan
        assert t2.stats == {"model_evals": 0, "cache_hits": 1}
        assert t2.cache.disk_hits == 1

    def test_fingerprint_change_invalidates(self, tmp_path):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        t.plan("matmul", 4096, device_count=8, platform="cpu",
               device_kind="kind-a")
        t.plan("matmul", 4096, device_count=8, platform="cpu",
               device_kind="kind-b")       # different hardware fingerprint
        assert t.stats["model_evals"] == 2
        t.plan("matmul", 4096, device_count=4, platform="cpu",
               device_kind="kind-a")       # different pool size
        assert t.stats["model_evals"] == 3

    def test_fingerprint_and_key_stability(self):
        fp1 = machine_fingerprint("m", "cpu", "k", 8)
        fp2 = machine_fingerprint("m", "cpu", "k", 8)
        assert fp1 == fp2 and len(fp1) == 12
        assert fp1 != machine_fingerprint("m", "cpu", "k", 9)
        assert plan_key(fp1, "matmul", 4096, 8, "float32") == \
            f"{fp1}-matmul-n4096-p8-float32"

    def test_corrupt_cache_entry_is_a_miss(self, tmp_path):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        plan = t.plan("matmul", 4096, device_count=8, platform="cpu",
                      device_kind="test-cpu")
        path = tmp_path / os.listdir(tmp_path)[0]
        path.write_text("{not json")
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        got = t2.plan("matmul", 4096, device_count=8, platform="cpu",
                      device_kind="test-cpu")
        assert got == plan and t2.stats["model_evals"] == 1

    def test_prefill_chunk(self):
        t = Tuner(cache=PlanCache.__new__(PlanCache))  # cache unused
        t.cache = None
        assert Tuner.prefill_chunk(t, 3) == 1
        assert Tuner.prefill_chunk(t, 8) == 8
        assert Tuner.prefill_chunk(t, 21) == 16
        assert Tuner.prefill_chunk(t, 4096) == 128


class TestPlanInvalidation:
    """Every way a cached plan can go stale must read as a miss."""

    KW = dict(device_count=8, platform="cpu", device_kind="test-cpu")

    def _plan_and_mutate(self, tmp_path, field, value):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        t.plan("matmul", 4096, **self.KW)
        path = tmp_path / os.listdir(tmp_path)[0]
        payload = json.loads(path.read_text())
        payload[field] = value
        path.write_text(json.dumps(payload))
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        t2.plan("matmul", 4096, **self.KW)
        return t2

    def _assert_replanned_and_repaired(self, t2, tmp_path):
        assert t2.stats["model_evals"] == 1      # stale entry read as a miss
        t3 = Tuner(cache=PlanCache(str(tmp_path)))
        t3.plan("matmul", 4096, **self.KW)       # replan rewrote a valid entry
        assert t3.stats["model_evals"] == 0 and t3.cache.disk_hits == 1

    def test_model_version_mismatch_replans(self, tmp_path):
        t2 = self._plan_and_mutate(tmp_path, "model_version", "ir-0-ancient")
        self._assert_replanned_and_repaired(t2, tmp_path)

    def test_plan_schema_bump_replans(self, tmp_path):
        from repro.tuner.plan import PLAN_SCHEMA
        t2 = self._plan_and_mutate(tmp_path, "schema", PLAN_SCHEMA + 1)
        self._assert_replanned_and_repaired(t2, tmp_path)

    def test_current_schema_is_a_hit(self, tmp_path):
        # control: untouched payload round-trips as a disk hit
        t = Tuner(cache=PlanCache(str(tmp_path)))
        t.plan("matmul", 4096, **self.KW)
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        t2.plan("matmul", 4096, **self.KW)
        assert t2.stats["model_evals"] == 0 and t2.cache.disk_hits == 1

    def test_drift_revision_bump_replans(self, tmp_path):
        from repro.tuner import build_default_registry
        from repro import telemetry
        reg = build_default_registry()
        t = Tuner(registry=reg, cache=PlanCache(str(tmp_path)))
        p1 = t.plan("matmul", 4096, **self.KW)
        t.plan("matmul", 4096, **self.KW)
        assert t.stats == {"model_evals": 1, "cache_hits": 1}
        telemetry.bump_revision(reg, "cpu-host")
        p2 = t.plan("matmul", 4096, **self.KW)
        assert t.stats["model_evals"] == 2       # stale plan never recalled
        assert p2.fingerprint != p1.fingerprint
        # the old entry is orphaned on disk, not misread
        assert len(os.listdir(tmp_path)) == 2


@pytest.fixture(scope="module")
def verdicts():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "drivers", "tuner_driver.py")],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestDispatchMultiDevice:
    @pytest.mark.parametrize("name", ["matmul_err", "trsm_err",
                                      "cholesky_err", "matmul_pallas_err",
                                      "trsm_pallas_err",
                                      "cholesky_pallas_err"])
    def test_numerics_match_reference(self, verdicts, name):
        assert verdicts[name] < 1e-4, f"{name}: rel err {verdicts[name]}"

    def test_repeat_call_served_from_cache(self, verdicts):
        assert verdicts["repeat_model_evals_delta"] == 0
        assert verdicts["cache_hits"] >= 1

    def test_fresh_tuner_hits_disk(self, verdicts):
        assert verdicts["fresh_tuner_model_evals"] == 0
        assert verdicts["fresh_tuner_disk_hits"] == 1

    def test_dispatched_variant_matches_select(self, verdicts):
        assert verdicts["plan_matches_select"] is True


class TestDispatchDiagSpan:
    """``repro.dispatch.execute`` carries how many diagonal blocks the
    local Pallas solves invert and how many they substitute, from the
    kernels' shape rule."""

    @staticmethod
    def _v5e_plan(tuner, op, n=16384, **grid):
        import dataclasses
        plan = tuner.plan(op, n, device_count=1, platform="tpu",
                          device_kind="TPU v5 lite", local_kernel="pallas",
                          use_cache=False)
        return dataclasses.replace(plan, **grid) if grid else plan

    @pytest.mark.parametrize("op,want", [("trsm", (128, 0)),
                                         ("cholesky", (30, 1))])
    def test_execute_span_carries_diag_split(self, tmp_path, op, want):
        """The linalg cell's plans (TRSM block 128, Cholesky block 512)
        read 128 / 0 and 30 / 1; a call on the CPU carries its own plan's
        counts on its execute span."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro import obs
        from repro.tuner import dispatch

        tuner = Tuner(cache=PlanCache(str(tmp_path / "plans")))
        big = self._v5e_plan(tuner, op)
        blocks = {"trsm": 128, "cholesky": 512}
        assert big.tiles[op]["block"] == blocks[op]
        assert dispatch._diag_args(big) == {"diag_inverted": want[0],
                                            "diag_substituted": want[1]}
        n = 768
        rng = np.random.default_rng(0)
        m = rng.standard_normal((n, n))
        if op == "trsm":
            operands = (np.triu(m) + 2 * np.sqrt(n) * np.eye(n), m)
        else:
            operands = (m @ m.T + n * np.eye(n),)
        operands = [jnp.asarray(x, jnp.float32) for x in operands]
        obs.reset()
        tr = obs.enable()
        try:
            jax.block_until_ready(getattr(dispatch, op)(
                *operands, tuner=tuner, local_kernel="pallas"))
            (span,) = [s for s in tr.spans() if s.name == "dispatch.execute"]
        finally:
            obs.reset()
        plan = tuner.plan(op, n, devices=jax.devices(),
                          local_kernel="pallas")
        args = dispatch._diag_args(plan)
        assert args["diag_inverted"] > 0
        assert {k: span.args[k] for k in args} == args

    @pytest.mark.parametrize("op,want", [("trsm", (128, 0)),
                                         ("cholesky", (92, 2))])
    def test_diag_split_per_device_on_a_grid(self, tmp_path, op, want):
        """On a 2x2 grid (2.5D for TRSM: rows over two layers) each of the
        g = 2 steps solves a local 8192-wide block; a Cholesky step adds
        its panel solve at block 256 (32 blocks, all inverted) to its
        local factor's 15 panels."""
        from repro.tuner import dispatch

        tuner = Tuner(cache=PlanCache(str(tmp_path / "plans")))
        grid = {"g": 2, "c": 2 if op == "trsm" else 1}
        plan = self._v5e_plan(tuner, op, **grid)
        assert dispatch._diag_args(plan) == {"diag_inverted": want[0],
                                             "diag_substituted": want[1]}
        jnp_plan = self._v5e_plan(tuner, op, local_kernel="jnp")
        assert dispatch._diag_args(jnp_plan) == {}
