"""Invariant tests for the 16 algorithm-variant models (paper §V)."""

import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (HOPPER, AlgoContext, CommModel, ComputeModel,
                        IdentityCalibration, ParametricCalibration)
from repro.core.perfmodel import HOPPER_EFFICIENCY
from repro.perf import ALGOS, VARIANTS, pct_of_peak
from repro.tuner import DEFAULT_REGISTRY
from repro.tuner.select import best_variant, legal_c_values, select

evaluate = DEFAULT_REGISTRY.evaluate

CTX = AlgoContext(CommModel(HOPPER, ParametricCalibration()),
                  ComputeModel(HOPPER, HOPPER_EFFICIENCY))
CTX_IDEAL = AlgoContext(CommModel(HOPPER, IdentityCalibration()),
                        ComputeModel(HOPPER, HOPPER_EFFICIENCY))

GRID_P = [64, 256, 1024, 4096]


class TestInvariants:
    @pytest.mark.parametrize("algo", ALGOS)
    @pytest.mark.parametrize("p", GRID_P)
    def test_all_variants_positive_and_decomposed(self, algo, p):
        for variant in VARIANTS:
            r = evaluate(CTX, algo, variant, 32768, p, c=4, r=2)
            assert r.total > 0
            assert r.comm > 0 and r.comp > 0
            # overlap can only help: total <= serialized comm + comp
            assert r.total <= r.comm + r.comp + 1e-12
            assert abs(sum(r.terms.values()) - r.total) < 1e-6 * r.total

    @pytest.mark.parametrize("algo", ALGOS)
    @pytest.mark.parametrize("p", GRID_P)
    def test_overlap_never_slower(self, algo, p):
        """max(comm, comp) composition can't exceed comm+comp (with the same
        thread count; the t-1 penalty can flip it on Hopper, so compare the
        overlapped *bound*, i.e. totals under identical compute terms)."""
        for base, ovlp in (("2d", "2d_ovlp"), ("2.5d", "2.5d_ovlp")):
            r1 = evaluate(CTX_IDEAL, algo, base, 65536, p, c=4, r=2)
            r2 = evaluate(CTX_IDEAL, algo, ovlp, 65536, p, c=4, r=2)
            # comm is never larger in the ovlp variant's serialized ledger
            assert r2.total <= (r1.total + r2.comp - r1.comp) * 1.05 + 1e-9

    def test_cannon_25d_c1_degenerates_to_2d(self):
        r2d = evaluate(CTX_IDEAL, "cannon", "2d", 32768, 1024)
        r25 = evaluate(CTX_IDEAL, "cannon", "2.5d", 32768, 1024, c=1)
        assert r25.total == pytest.approx(r2d.total, rel=0.1)

    def test_more_cores_less_time(self):
        for algo in ALGOS:
            t_small = evaluate(CTX, algo, "2d", 65536, 256, r=2).total
            t_big = evaluate(CTX, algo, "2d", 65536, 4096, r=2).total
            assert t_big < t_small

    @given(n=st.sampled_from([16384, 32768, 65536, 131072]),
           p=st.sampled_from(GRID_P))
    @settings(max_examples=40, deadline=None)
    def test_pct_of_peak_in_range(self, n, p):
        for algo in ALGOS:
            for variant in VARIANTS:
                r = evaluate(CTX, algo, variant, n, p, c=4, r=2)
                pct = pct_of_peak(CTX, r)
                assert 0 < pct <= 100.0

    def test_cannon_flop_conservation(self):
        """Compute time x peak x eff == 2n^3 exactly for Cannon 2D."""
        n, p = 32768, 1024
        r = evaluate(CTX_IDEAL, "cannon", "2d", n, p)
        bs = n / math.sqrt(p)
        eff = HOPPER_EFFICIENCY["dgemm"](bs)
        implied = r.comp * p * HOPPER.peak_flops_per_unit * eff
        assert implied == pytest.approx(2 * n ** 3, rel=1e-6)

    def test_trsm_update_flops_conserved(self):
        """The dominant dgemm term sums to ~n^3/p per process."""
        n, p, r_ = 65536, 1024, 2
        res = evaluate(CTX_IDEAL, "trsm", "2d", n, p, r=r_)
        bs = n / (r_ * math.sqrt(p))
        eff = HOPPER_EFFICIENCY["dgemm"](bs)
        flops = res.terms["update"] * HOPPER.peak_flops_per_unit * eff
        assert flops == pytest.approx(n ** 3 / p, rel=0.05)


class TestPredictor:
    def test_legal_c_values(self):
        import math
        for p in (256, 1024, 4096, 65536):
            cs = legal_c_values(p)
            assert cs, p
            for c in cs:
                g = math.sqrt(p / c)
                assert abs(g - round(g)) < 1e-9      # square grid
                assert c <= max(2, round(p ** (1 / 3)))  # Solomonik bound

    def test_best_variant_structure(self):
        ch = best_variant(CTX, "cannon", 32768, 1024)
        assert set(ch) == set(VARIANTS)
        for v, choice in ch.items():
            assert choice.result.total > 0

    def test_memory_constraint_limits_c(self):
        """At huge n, 2.5D replication must not exceed per-process memory."""
        ch = best_variant(CTX, "cannon", 262144, 1024)
        c = ch["2.5d"].result.c
        words = 3 * 262144 ** 2 * c / 1024
        assert words * 8 <= HOPPER.mem_per_unit * 1.01

    def test_select_returns_fastest(self):
        ch = best_variant(CTX, "summa", 32768, 4096)
        best = select(CTX, "summa", 32768, 4096)
        assert best.result.total == min(c.result.total for c in ch.values())

    def test_communication_avoidance_wins_at_scale(self):
        """The paper's headline: at fixed n, growing p eventually favors
        2.5D over 2D (communication avoidance pays at scale)."""
        n = 32768
        gap_small = (best_variant(CTX, "cannon", n, 256)["2d_ovlp"].result.total
                     / best_variant(CTX, "cannon", n, 256)["2.5d_ovlp"].result.total)
        gap_big = (best_variant(CTX, "cannon", n, 65536)["2d_ovlp"].result.total
                   / best_variant(CTX, "cannon", n, 65536)["2.5d_ovlp"].result.total)
        assert gap_big > gap_small  # 2.5D relatively better at scale
