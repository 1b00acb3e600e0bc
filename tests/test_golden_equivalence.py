"""Golden-equivalence: the cost-IR programs reproduce the pre-IR closed
forms exactly.

``tests/golden/model_values.json`` snapshots every (algo, variant) over a
scenario grid — n x p x c x r, with both the parametric and the identity
calibration — as computed by the closed-form Python models before the IR
rewrite.  These fixtures pin the DESIGN.md §1.1-1.3 transcription choices
(2.5D step count, TRSM update multiplicity, collective volumes) through
any future refactor: a model change that alters predictions must
consciously regenerate the goldens.
"""

import json
import os

import numpy as np
import pytest

from repro.core import (HOPPER, AlgoContext, CommModel, ComputeModel,
                        IdentityCalibration, ParametricCalibration)
from repro.core.perfmodel import HOPPER_EFFICIENCY
from repro.perf import PROGRAMS, evaluate_program
from repro.tuner import DEFAULT_REGISTRY

evaluate = DEFAULT_REGISTRY.evaluate

GOLD_PATH = os.path.join(os.path.dirname(__file__), "golden",
                         "model_values.json")
REL_TOL = 1e-9

CTX = {
    "param": AlgoContext(CommModel(HOPPER, ParametricCalibration()),
                         ComputeModel(HOPPER, HOPPER_EFFICIENCY)),
    "identity": AlgoContext(CommModel(HOPPER, IdentityCalibration()),
                            ComputeModel(HOPPER, HOPPER_EFFICIENCY)),
}


def _load():
    with open(GOLD_PATH) as f:
        return json.load(f)["entries"]


ENTRIES = _load()
KEYS = sorted({(e["algo"], e["variant"]) for e in ENTRIES})


@pytest.mark.parametrize("algo,variant", KEYS)
def test_scalar_matches_golden(algo, variant):
    """The scalar evaluation (IR program, 0-d env) reproduces every golden
    cell: totals, ledgers, and each named term."""
    for e in ENTRIES:
        if (e["algo"], e["variant"]) != (algo, variant):
            continue
        res = evaluate(CTX[e["calibration"]], algo, variant,
                       e["n"], e["p"], c=e["c"], r=e["r"])
        for field in ("total", "comm", "comp"):
            want = e[field]
            assert getattr(res, field) == pytest.approx(want, rel=REL_TOL), \
                (e, field)
        for name, want in e["terms"].items():
            assert name in res.terms, (e, name)
            assert res.terms[name] == pytest.approx(want, rel=REL_TOL,
                                                    abs=1e-300), (e, name)
        # terms the IR adds beyond the closed forms (e.g. an identically
        # zero layer_reduce at c=1) must actually be zero
        for name, got in res.terms.items():
            if name not in e["terms"]:
                assert got == pytest.approx(0.0, abs=1e-300), (e, name)


@pytest.mark.parametrize("algo,variant", KEYS)
def test_vectorized_matches_golden(algo, variant):
    """One vectorized pass over all of a variant's golden scenarios equals
    the per-scenario scalar values."""
    for cal, ctx in CTX.items():
        rows = [e for e in ENTRIES
                if (e["algo"], e["variant"]) == (algo, variant)
                and e["calibration"] == cal]
        ns = np.array([e["n"] for e in rows], dtype=float)
        ps = np.array([e["p"] for e in rows], dtype=float)
        cs = np.array([e["c"] for e in rows], dtype=float)
        rs = np.array([e["r"] for e in rows], dtype=float)
        res = evaluate_program(PROGRAMS[(algo, variant)], ctx, ns, ps, cs, rs)
        want_tot = np.array([e["total"] for e in rows])
        want_comm = np.array([e["comm"] for e in rows])
        want_comp = np.array([e["comp"] for e in rows])
        np.testing.assert_allclose(res.total, want_tot, rtol=REL_TOL)
        np.testing.assert_allclose(res.comm, want_comm, rtol=REL_TOL)
        np.testing.assert_allclose(res.comp, want_comp, rtol=REL_TOL)
