"""Tests for the repro.perf cost-IR: vectorized evaluation semantics,
estimator-flavor options, LU end-to-end registration/tuning, and the
plan-cache model-version invalidation."""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import (HOPPER, AlgoContext, CommModel, ComputeModel,
                        IdentityCalibration, ParametricCalibration)
from repro.core.perfmodel import HOPPER_EFFICIENCY
from repro.perf import (Collective, Compute, EvalOptions, Loop, N, Overlap,
                        P, P2P, PROGRAMS, Program, Seq, SyncP2P, T,
                        evaluate_program, sqrt)
from repro.tuner import DEFAULT_REGISTRY, PlanCache, Tuner
from repro.tuner import select

evaluate = DEFAULT_REGISTRY.evaluate

CTX = AlgoContext(CommModel(HOPPER, ParametricCalibration()),
                  ComputeModel(HOPPER, HOPPER_EFFICIENCY))


class TestVectorizedEvaluation:
    def test_grid_matches_scalar_loop(self):
        ns = np.array([16384.0, 32768.0, 65536.0])
        ps = np.array([256.0, 1024.0, 4096.0])
        Ng, Pg = np.meshgrid(ns, ps, indexing="ij")
        for key in (("cannon", "2.5d_ovlp"), ("trsm", "2d_ovlp"),
                    ("cholesky", "2.5d"), ("lu", "2.5d")):
            res = evaluate_program(PROGRAMS[key], CTX, Ng, Pg, 4, 2)
            assert res.total.shape == (3, 3)
            for i in range(3):
                for j in range(3):
                    want = evaluate(CTX, key[0], key[1], int(ns[i]),
                                    int(ps[j]), c=4, r=2)
                    assert res.total[i, j] == pytest.approx(want.total,
                                                            rel=1e-12)
                    assert res.comm[i, j] == pytest.approx(want.comm,
                                                           rel=1e-12)

    def test_phase_breakdown_sums_to_total(self):
        ns = np.array([16384.0, 65536.0])
        res = evaluate_program(PROGRAMS[("summa", "2.5d_ovlp")], CTX,
                               ns, 1024.0, 4.0, 1.0)
        summed = sum(ph.exposed for ph in res.phases.values())
        np.testing.assert_allclose(summed, res.total, rtol=1e-12)
        # overlap can only help: exposed <= serialized comm + comp
        assert np.all(res.total <= res.comm + res.comp + 1e-12)

    def test_registry_grid_evaluation(self):
        res = DEFAULT_REGISTRY.evaluate_grid(
            CTX, "cannon", "2d", np.array([32768.0, 65536.0]), 1024.0)
        assert res.total.shape == (2,)
        assert np.all(res.total > 0)


class TestCalibrationTableVectorized:
    def _table(self):
        from repro.core import CalibrationTable
        return CalibrationTable(
            avg={1.0: 1.1, 4.0: 1.4, 32.0: 2.2},
            mx={(64.0, 1.0): 1.3, (64.0, 4.0): 1.9, (64.0, 32.0): 3.0,
                (1024.0, 1.0): 1.6, (1024.0, 4.0): 2.4, (1024.0, 32.0): 4.1},
            extrapolation_degree=1)

    def test_vec_matches_scalar_surfaces(self):
        """The closed-form numpy overrides equal the scalar methods across
        interpolation, clamping, and the beyond-range extrapolation — so
        tabulated (fitted) calibrations keep the vectorization win."""
        tab = self._table()
        ds = np.array([0.5, 1.0, 2.0, 4.0, 10.0, 32.0, 100.0])
        ps = np.array([16.0, 64.0, 300.0, 1024.0, 4096.0, 65536.0])
        np.testing.assert_allclose(
            tab.c_avg_vec(ds), [tab.c_avg(d) for d in ds], rtol=1e-12)
        Pg, Dg = np.meshgrid(ps, ds, indexing="ij")
        want = [[tab.c_max(p, d) for d in ds] for p in ps]
        np.testing.assert_allclose(tab.c_max_vec(Pg, Dg), want, rtol=1e-12)

    def test_ir_with_table_calibration_matches_scalar(self):
        tab = self._table()
        ctx = AlgoContext(CommModel(HOPPER, tab),
                          ComputeModel(HOPPER, HOPPER_EFFICIENCY))
        ns = np.array([16384.0, 65536.0])
        res = evaluate_program(PROGRAMS[("summa", "2.5d")], ctx,
                               ns, 4096.0, 4.0, 1.0)
        for i, n in enumerate(ns):
            want = evaluate(ctx, "summa", "2.5d", int(n), 4096, c=4)
            assert res.total[i] == pytest.approx(want.total, rel=1e-12)


class TestEvalOptions:
    def test_modes_are_ordered(self):
        """est_Cal >= est_NoCal >= est_ideal, selected by options alone
        (no context rebuilding)."""
        cal = evaluate(CTX, "summa", "2d", 32768, 1024).total
        nocal = evaluate(CTX, "summa", "2d", 32768, 1024,
                         options=EvalOptions("nocal")).total
        ideal = evaluate(CTX, "summa", "2d", 32768, 1024,
                         options=EvalOptions("ideal")).total
        assert cal > nocal >= ideal

    def test_nocal_equals_identity_context(self):
        """mode="nocal" must equal evaluating with IdentityCalibration —
        the old way of getting est_NoCal."""
        ctx_id = AlgoContext(CommModel(HOPPER, IdentityCalibration()),
                             ComputeModel(HOPPER, HOPPER_EFFICIENCY))
        for key in (("cannon", "2.5d"), ("trsm", "2d"), ("lu", "2d")):
            a = evaluate(CTX, key[0], key[1], 32768, 1024, c=4, r=2,
                         options=EvalOptions("nocal")).total
            b = evaluate(ctx_id, key[0], key[1], 32768, 1024, c=4, r=2).total
            assert a == pytest.approx(b, rel=1e-12)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            EvalOptions("bogus")


class TestLU:
    """LU 2D/2.5D: authored as <50-line IR programs, registered and
    tunable end-to-end with zero selection/tuner changes."""

    def test_registered(self):
        assert "lu" in DEFAULT_REGISTRY.algos()
        assert set(DEFAULT_REGISTRY.variants("lu")) == {"2d", "2.5d"}

    def test_flop_conservation(self):
        """The trailing-update dgemm term sums to ~2n^3/(3p) per process."""
        ctx_id = AlgoContext(CommModel(HOPPER, IdentityCalibration()),
                             ComputeModel(HOPPER, HOPPER_EFFICIENCY))
        n, p, r = 65536, 1024, 2
        res = evaluate(ctx_id, "lu", "2d", n, p, r=r)
        import math
        bs = n / (r * math.sqrt(p))
        eff = HOPPER_EFFICIENCY["dgemm"](bs)
        flops = res.terms["update"] * HOPPER.peak_flops_per_unit * eff
        assert flops == pytest.approx(2 * n ** 3 / (3 * p), rel=0.05)

    def test_lu_slower_than_cholesky_2x_matmul_relation(self):
        """Sanity ordering at one scenario: LU does 2x Cholesky's flops, so
        with the same layout it should cost more than Cholesky."""
        lu = evaluate(CTX, "lu", "2d", 32768, 1024, r=2).total
        ch = evaluate(CTX, "cholesky", "2d", 32768, 1024, r=2).total
        assert lu > ch

    def test_selectable_by_predictor(self):
        ch = select.best_variant(CTX, "lu", 32768, 1024)
        assert set(ch) == {"2d", "2.5d"}
        best = select.select(CTX, "lu", 32768, 1024)
        assert best.result.total == min(c.result.total for c in ch.values())
        assert 0 < best.pct_peak <= 100

    def test_tunes_end_to_end(self, tmp_path):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        plan = t.plan("lu", 8192, device_count=16, platform="cpu",
                      device_kind="test-cpu")
        assert plan.algo == "lu"
        assert plan.variant in ("2d", "2.5d")
        assert plan.p <= 16 and plan.predicted["total"] > 0
        again = t.plan("lu", 8192, device_count=16, platform="cpu",
                       device_kind="test-cpu")
        assert again == plan and t.stats["cache_hits"] == 1

    def test_crossover_tolerates_missing_ovlp_variants(self):
        """lu has no *_ovlp models: crossover must return None, not KeyError
        (the satellite fix for select.crossover_core_count)."""
        assert select.crossover_core_count(
            CTX, "lu", 32768, [1536, 24576]) is None


class TestPredictorMissingVariants:
    def test_format_table_tolerates_dropped_variant(self):
        """A cell whose 2.5D variants were dropped (memory-infeasible under
        pinned c_values) renders as a dash, not a KeyError."""
        tbl = select.prediction_table(CTX, "cannon", [262144], [1536],
                                      c_values=[64])
        row = tbl[262144][1536]
        assert "2.5d" not in row          # dropped: 64-way replication OOMs
        out = select.format_table(tbl, "cannon")
        assert "—" in out and "2d" in out

    def test_crossover_skips_infeasible_cells(self):
        """With pinned c_values making 2.5D infeasible at low p, crossover
        scans past those cells instead of KeyError'ing."""
        cores = [1536, 6144, 24576, 98304, 393216]
        cx = select.crossover_core_count(CTX, "cannon", 32768, cores)
        # same answer as comparing the two tuned variants cell by cell
        want = None
        for co in cores:
            p = max(1, co // HOPPER.threads_per_unit)
            ch = select.best_variant(CTX, "cannon", 32768, p)
            if ch["2.5d_ovlp"].result.total < ch["2d_ovlp"].result.total:
                want = co
                break
        assert cx == want

    def test_batched_best_variant_equals_per_cell(self):
        cells = [(16384, 256), (32768, 1024), (65536, 4096)]
        batch = select.best_variant_batch(CTX, "trsm", cells)
        for cell, got in zip(cells, batch):
            solo = select.best_variant(CTX, "trsm", *cell)
            assert set(got) == set(solo)
            for v in got:
                assert got[v].result.total == pytest.approx(
                    solo[v].result.total, rel=1e-12)
                assert got[v].result.c == solo[v].result.c
                assert got[v].result.r == solo[v].result.r


class TestPlanModelVersioning:
    def _plan(self, tmp_path):
        t = Tuner(cache=PlanCache(str(tmp_path)))
        return t, t.plan("matmul", 4096, device_count=8, platform="cpu",
                         device_kind="test-cpu")

    def test_payload_carries_versions(self, tmp_path):
        _, plan = self._plan(tmp_path)
        d = plan.to_dict()
        from repro.tuner.plan import PLAN_SCHEMA
        from repro.perf import MODEL_VERSION
        assert d["schema"] == PLAN_SCHEMA
        assert d["model_version"] == MODEL_VERSION

    def test_stale_model_version_is_invalidated(self, tmp_path):
        t, plan = self._plan(tmp_path)
        files = os.listdir(tmp_path)
        assert len(files) == 1
        path = tmp_path / files[0]
        payload = json.loads(path.read_text())
        payload["model_version"] = "ir-0-older-equations"
        path.write_text(json.dumps(payload))
        # a fresh tuner must re-plan (stale model, not silently served) and
        # rewrite the entry with the current version
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        got = t2.plan("matmul", 4096, device_count=8, platform="cpu",
                      device_kind="test-cpu")
        assert t2.stats["model_evals"] == 1
        assert got == plan
        from repro.perf import MODEL_VERSION
        assert json.loads(path.read_text())["model_version"] == MODEL_VERSION

    def test_pre_versioning_schema_is_invalidated(self, tmp_path):
        """A PR-1-era payload (schema 1, no model_version) reads as a miss."""
        t, plan = self._plan(tmp_path)
        path = tmp_path / os.listdir(tmp_path)[0]
        payload = json.loads(path.read_text())
        payload["schema"] = 1
        payload.pop("model_version")
        path.write_text(json.dumps(payload))
        t2 = Tuner(cache=PlanCache(str(tmp_path)))
        t2.plan("matmul", 4096, device_count=8, platform="cpu",
                device_kind="test-cpu")
        assert t2.stats["model_evals"] == 1


class TestAuthoringAPI:
    def test_toy_model_under_custom_registry(self):
        """Authoring win: a new model is a handful of IR lines, registered
        and immediately tunable (the quickstart example, as a test)."""
        from repro.tuner import PerfModelRegistry
        sp = sqrt(P)
        bs = N / sp
        w = bs * bs
        ring = Program(
            "ring_matmul", "2d",
            Seq(("allgather_A", Collective("allgather", w, q=sp, dist=1)),
                ("dgemm", Loop(Compute("dgemm", bs, T), sp)),
                ("reduce_C", Collective("reduce", w, q=sp, dist=sp))))
        reg = PerfModelRegistry()
        reg.register_program(ring)
        res = reg.evaluate(CTX, "ring_matmul", "2d", 32768, 1024)
        assert res.total > 0 and set(res.terms) == {"allgather_A", "dgemm",
                                                    "reduce_C"}
        grid = reg.evaluate_grid(CTX, "ring_matmul", "2d",
                                 np.array([16384.0, 32768.0]), 1024.0)
        assert grid.total.shape == (2,)
        assert grid.total[1] == pytest.approx(res.total, rel=1e-12)

    def test_overlap_never_exceeds_serial(self):
        body = Overlap(P2P(N * N / P, 1.0), Compute("dgemm", N / sqrt(P), T),
                       count=sqrt(P))
        prog = Program("toy", "ovlp", Seq(("loop", body)))
        res = evaluate_program(prog, CTX, 32768, 1024)
        assert float(res.total) <= float(res.comm) + float(res.comp)

    def test_sync_p2p_at_least_p2p(self):
        a = evaluate_program(Program("t", "a", Seq(("x", P2P(1e6, 8.0)))),
                             CTX, 1, 4096)
        b = evaluate_program(Program("t", "b", Seq(("x", SyncP2P(1e6, 8.0)))),
                             CTX, 1, 4096)
        assert float(b.total) >= float(a.total)
