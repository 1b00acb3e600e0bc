"""repro.obs: span semantics, metrics, exporter pairing, three-tier
integration, and the sim/serving wiring."""

import json
import logging
import math

import numpy as np
import pytest

from repro import obs, telemetry
from repro.obs import (MetricsRegistry, Tracer, export_spans,
                       parse_prometheus_text, sim_trace, tier_of)


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    monkeypatch.delenv("REPRO_TELEMETRY", raising=False)
    obs.reset()
    telemetry.reset()
    yield
    obs.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# span semantics
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_ids(self):
        tr = Tracer()
        with tr.span("outer", cat="dispatch") as outer:
            with tr.span("inner", cat="kernel") as inner:
                assert tr.current() is inner
            assert tr.current() is outer
        assert tr.current() is None
        inner_sp, outer_sp = tr.spans()          # closed in inner-first order
        assert inner_sp.name == "inner"
        assert inner_sp.parent_id == outer_sp.span_id
        assert inner_sp.trace_id == outer_sp.trace_id == outer_sp.span_id
        assert outer_sp.parent_id is None
        assert outer_sp.dur_s >= inner_sp.dur_s >= 0.0

    def test_exception_safe_close(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("nope")
        (sp,) = tr.spans()
        assert sp.error is True
        assert sp.dur_s >= 0.0                  # duration still recorded
        assert tr.current() is None             # stack not corrupted

    def test_exception_closes_skipped_children(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                tr.begin("dangling")            # never explicitly ended
                raise RuntimeError
        assert tr.current() is None
        assert {s.name for s in tr.spans()} == {"outer"}

    def test_ring_buffer_drops_and_counts(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            tr.complete(f"s{i}", 0.001)
        assert len(tr.spans()) == 4
        assert tr.dropped == 6
        assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]

    def test_residual_and_rel_err(self):
        tr = Tracer()
        sp = tr.complete("x", 0.2, predicted_s=0.1)
        assert sp.residual_s == pytest.approx(0.1)
        assert sp.rel_err == pytest.approx(0.5)
        unpaired = tr.complete("y", 0.2)
        assert unpaired.residual_s is None and unpaired.rel_err is None

    def test_maybe_span_disabled_is_shared_noop(self):
        # off: the hook is the profiler annotation alone, named
        # repro.<name>, and nothing enters the tracer's ring
        from jax.profiler import TraceAnnotation
        obs.disable()
        c1 = obs.maybe_span("a", cat="dispatch", n=3)
        assert isinstance(c1, TraceAnnotation)
        with c1 as entered:
            entered.set_metadata(done=1)
        assert obs.tracer().spans() == []
        assert obs.tracer().n_closed == 0

    def test_alert_counts_and_marks(self):
        obs.enable()
        obs.alert("drift", op="summa")
        obs.alert("drift", op="trsm")
        (c,) = [m for m in obs.default_registry().metrics()
                if m.name == "obs_alerts_total"]
        assert c.value == 2
        kinds = [s for s in obs.tracer().spans() if s.kind == "instant"]
        assert len(kinds) == 2 and all(s.cat == "alert" for s in kinds)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_histogram_bucket_boundaries_le_inclusive(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(1.0, 2.0, 5.0))
        for v in (1.0, 1.0000001, 2.0, 5.0, 6.0, 0.5):
            h.observe(v)
        # counts per bucket: le=1 gets {1.0, 0.5}; le=2 gets
        # {1.0000001, 2.0}; le=5 gets {5.0}; +Inf gets {6.0}
        assert h.counts == [2, 2, 1, 1]
        assert h.count == 6
        assert h.min == 0.5 and h.max == 6.0

    def test_histogram_exact_percentile_matches_nearest_rank(self):
        from repro.serving.trace import _percentile
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(1.0,), keep_values=True)
        vals = [0.3, 1.7, 0.9, 4.2, 2.2, 0.1, 3.3]
        for v in vals:
            h.observe(v)
        for q in (0, 50, 95, 99, 100):
            assert h.percentile(q) == _percentile(vals, q)

    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("n", kind="x")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_tracks_max(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        for v in (3, 9, 1):
            g.set(v)
        assert g.value == 1 and g.max_value == 9

    def test_get_or_create_and_kind_mismatch(self):
        reg = MetricsRegistry()
        assert reg.counter("a", op="x") is reg.counter("a", op="x")
        assert reg.counter("a", op="y") is not reg.counter("a", op="x")
        with pytest.raises(TypeError):
            reg.gauge("a", op="x")

    def test_prometheus_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("steps_total", policy="fifo").inc(7)
        reg.gauge("queue_depth").set(3.5)
        h = reg.histogram("ttft_s", buckets=(0.1, 1.0), policy="fifo")
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        parsed = parse_prometheus_text(reg.prometheus_text())
        assert parsed['steps_total{policy="fifo"}'] == 7.0
        assert parsed["queue_depth"] == 3.5
        assert parsed['ttft_s_bucket{le="0.1",policy="fifo"}'] == 1.0
        assert parsed['ttft_s_bucket{le="1",policy="fifo"}'] == 2.0  # cumulative
        assert parsed['ttft_s_bucket{le="+Inf",policy="fifo"}'] == 3.0
        assert parsed['ttft_s_count{policy="fifo"}'] == 3.0
        assert parsed['ttft_s_sum{policy="fifo"}'] == pytest.approx(2.55)

    def test_snapshot_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        path = str(tmp_path / "m.jsonl")
        reg.dump_jsonl(path)
        reg.dump_jsonl(path)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["metrics"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# exporter pairing
# ---------------------------------------------------------------------------

def _events(doc, ph=None, pid=None):
    out = []
    for ev in doc["traceEvents"]:
        if ph is not None and ev.get("ph") != ph:
            continue
        if pid is not None and ev.get("pid") != pid:
            continue
        out.append(ev)
    return out


class TestExport:
    def test_pairing_rule(self):
        tr = Tracer()
        tr.complete("execute", 0.02, cat="dispatch", predicted_s=0.015)
        tr.complete("unpaired", 0.01, cat="dispatch")
        doc = json.loads(json.dumps(export_spans(tr.spans())))

        measured = [e for e in _events(doc, "X", 0)
                    if e["name"] == "execute"]
        predicted = [e for e in _events(doc, "X", 1)
                     if e["name"] == "execute"]
        assert len(measured) == len(predicted) == 1
        m, p = measured[0], predicted[0]
        assert m["ts"] == p["ts"]                    # same start
        assert m["dur"] == pytest.approx(0.02e6)
        assert p["dur"] == pytest.approx(0.015e6)
        assert m["args"]["residual_s"] == pytest.approx(0.005)
        assert m["args"]["rel_err"] == pytest.approx(0.25)
        assert p["args"]["pair_of"] == m["args"]["span_id"]
        # flow arrow links the pair
        starts = _events(doc, "s")
        ends = _events(doc, "f")
        assert len(starts) == len(ends) == 1
        assert starts[0]["id"] == ends[0]["id"]
        # the unpaired span has no predicted twin
        assert not [e for e in _events(doc, "X", 1)
                    if e["name"] == "unpaired"]
        assert doc["otherData"]["n_paired"] == 1

    def test_error_and_instant_events(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("bad"):
                raise RuntimeError
        tr.instant("alarm", cat="alert", args={"op": "x"})
        doc = export_spans(tr.spans())
        (bad,) = [e for e in _events(doc, "X") if e["name"] == "bad"]
        assert bad["args"]["error"] is True
        (inst,) = _events(doc, "i")
        assert inst["name"] == "alarm" and inst["cat"] == "alert"

    def test_tier_of(self):
        assert tier_of("kernel") == "kernel"
        assert tier_of("dispatch") == "op"
        assert tier_of("manual") == "op"
        assert tier_of("serve_step") == "serve"
        assert tier_of("alert") is None


# ---------------------------------------------------------------------------
# sim trace: cap fix + predicted overlay
# ---------------------------------------------------------------------------

class _FakePhase:
    def __init__(self, start, exposed):
        self.start = np.asarray(start, float)
        self.exposed = np.asarray(exposed, float)


class _FakeSim:
    algo, variant, topology = "summa", "2d", "torus"
    n, p = 1024.0, 4
    critical_rank = 1

    phases = {
        "bcast": _FakePhase([0.0, 0.0, 0.0, 0.0], [0.1, 0.2, 0.1, 0.1]),
        "dgemm": _FakePhase([0.1, 0.2, 0.1, 0.1], [1.0, 1.1, 1.0, 1.0]),
    }

    def summary(self):
        return {"total_s": 1.3}


class _FakeEval:
    phases = {"bcast": _FakePhase([0.0], [0.15]),
              "dgemm": _FakePhase([0.0], [1.05])}


class TestSimTrace:
    def test_cap_warns_and_annotates(self, caplog):
        with caplog.at_level(logging.WARNING, logger="repro.obs"):
            doc = sim_trace(_FakeSim(), max_ranks=2)
        assert any("truncated to 2 of 4 ranks" in r.message
                   for r in caplog.records)
        assert doc["otherData"]["ranks_shown"] == 2
        assert doc["otherData"]["ranks_dropped"] == 2
        tids = {e["tid"] for e in _events(doc, "X")}
        assert tids == {0, 1}

    def test_no_cap_no_warning(self, caplog):
        with caplog.at_level(logging.WARNING, logger="repro.obs"):
            doc = sim_trace(_FakeSim(), max_ranks=64)
        assert not caplog.records
        assert doc["otherData"]["ranks_dropped"] == 0
        assert {e["tid"] for e in _events(doc, "X", 0)} == {0, 1, 2, 3}

    def test_eval_overlay_pairs_critical_rank(self):
        doc = sim_trace(_FakeSim(), eval_result=_FakeEval())
        pred = _events(doc, "X", 1)
        assert [e["name"] for e in pred] == ["bcast", "dgemm"]
        (dg,) = [e for e in pred if e["name"] == "dgemm"]
        # measured on critical rank 1 is 1.1; predicted 1.05
        assert dg["args"]["measured_s"] == pytest.approx(1.1)
        assert dg["args"]["residual_s"] == pytest.approx(1.1 - 1.05)
        assert len(_events(doc, "s")) == 2        # one flow per phase
        resid = doc["otherData"]["phase_residual_s"]
        assert resid["bcast"] == pytest.approx(0.2 - 0.15)

    def test_simresult_chrome_trace_accepts_eval(self):
        # the SimResult method passes eval_result through (exercised with
        # the real engine in test_sim; the signature must exist)
        import inspect
        from repro.sim.result import SimResult
        sig = inspect.signature(SimResult.chrome_trace)
        assert "eval_result" in sig.parameters


# ---------------------------------------------------------------------------
# telemetry wiring: PhaseTimer as span emitter
# ---------------------------------------------------------------------------

class TestPhaseTimerSpans:
    def test_phase_emits_paired_span(self):
        obs.enable()
        pt = telemetry.PhaseTimer("summa", variant="2d", n=256, p=4,
                                  kind="dispatch",
                                  predicted={"total": 0.5, "comm": 0.2})
        with pt.phase("execute"):
            pass
        (sp,) = obs.tracer().spans()
        assert sp.cat == "dispatch" and sp.name == "execute"
        assert sp.predicted_s == 0.5              # execute -> total fallback
        assert sp.dur_s == pytest.approx(pt.phases["execute"])
        assert sp.args["op"] == "summa"

    def test_phase_span_records_error(self):
        obs.enable()
        pt = telemetry.PhaseTimer("x")
        with pytest.raises(KeyError):
            with pt.phase("execute"):
                raise KeyError("dead")
        (sp,) = obs.tracer().spans()
        assert sp.error is True
        assert pt.phases["execute"] >= 0.0        # accounting still happened

    def test_disabled_no_spans_and_shared_null(self):
        from repro.telemetry.record import _NULL, phase_scope
        assert phase_scope(None, "a") is _NULL
        assert phase_scope(None, "b") is _NULL
        pt = telemetry.PhaseTimer("x")
        with pt.phase("execute"):
            pass
        assert obs.tracer().spans() == []


# ---------------------------------------------------------------------------
# serving replay through the registry
# ---------------------------------------------------------------------------

class TestReplayRegistry:
    def _cost(self):
        from repro.configs import get
        from repro.core.machine import CPU_HOST
        from repro.serving.cost import cost_model_for
        return cost_model_for(get("qwen1.5-4b").reduced(), CPU_HOST)

    def test_report_agrees_with_registry(self):
        from repro.serving.trace import (TraceConfig, replay_traced,
                                         synthesize_trace)
        cost = self._cost()
        trace = synthesize_trace(TraceConfig(n_requests=60, seed=5))
        rep, reports, reg = replay_traced(trace, cost, policy="fifo")
        assert rep.n_finished == 60
        ttft = reg.histogram("serve_ttft_s", keep_values=True, policy="fifo")
        tpot = reg.histogram("serve_tpot_s", keep_values=True, policy="fifo")
        assert rep.ttft_p50_s == ttft.percentile(50)
        assert rep.ttft_p99_s == ttft.percentile(99)
        assert rep.tpot_p95_s == tpot.percentile(95)
        assert ttft.count == 60
        assert rep.tokens_out == int(
            reg.counter("serve_tokens_out_total", policy="fifo").value)
        met = int(reg.counter("serve_slo_met_total", policy="fifo").value)
        assert rep.slo_met_fraction == pytest.approx(met / 60)
        assert rep.makespan_s == pytest.approx(
            reg.gauge("serve_last_finish_s", policy="fifo").max_value)
        assert rep.goodput_rps == pytest.approx(met / rep.makespan_s)
        # step reports carry system state for the counter tracks
        assert any(r.decode_batch > 0 for r in reports)
        assert all(r.kv_blocks_total > 0 for r in reports)

    def test_replay_matches_request_metrics_recomputation(self):
        """The registry-driven report equals the old private-dict math."""
        import dataclasses as dc
        from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                             SimBackend)
        from repro.serving.trace import (TraceConfig, _percentile, replay,
                                         synthesize_trace)
        cost = self._cost()
        trace = synthesize_trace(TraceConfig(n_requests=40, seed=9))
        rep = replay(trace, cost, policy="fifo")
        sched = Scheduler(SimBackend(), cost, SchedulerConfig())
        for req in trace:
            sched.submit(dc.replace(req))
        sched.run()
        metrics = sched.request_metrics()
        ttft = [m["ttft_s"] for m in metrics if m["ttft_s"] is not None]
        tpot = [m["tpot_s"] for m in metrics if m["n_out"] > 1]
        assert rep.ttft_p95_s == pytest.approx(_percentile(ttft, 95))
        assert rep.tpot_p50_s == pytest.approx(_percentile(tpot, 50))
        assert rep.tokens_out == sum(m["n_out"] for m in metrics)
        assert rep.makespan_s == pytest.approx(
            max(m["finish_s"] for m in metrics))

    def test_serving_trace_export(self):
        from repro.obs import serving_trace
        from repro.serving.trace import (TraceConfig, replay_traced,
                                         synthesize_trace)
        cost = self._cost()
        trace = synthesize_trace(TraceConfig(n_requests=25, seed=1))
        rep, reports, _ = replay_traced(trace, cost, policy="model")
        doc = json.loads(json.dumps(serving_trace(
            reports, other_data=rep.to_dict())))
        steps_m = [e for e in _events(doc, "X", 0)
                   if e.get("cat") == "serve_step"
                   and e["name"].startswith("step ")]
        steps_p = [e for e in _events(doc, "X", 1)
                   if e.get("cat") == "serve_step"
                   and e["name"].startswith("step ")]
        assert len(steps_m) == len(steps_p) == len(reports)
        # pure replay: measured == predicted, residual exactly 0
        assert all(e["args"]["residual_s"] == 0.0 for e in steps_m)
        assert len(_events(doc, "s")) >= len(reports)
        counters = {e["name"] for e in _events(doc, "C")}
        assert {"queue", "kv_blocks", "batch"} <= counters
        assert doc["otherData"]["policy"] == rep.policy


# ---------------------------------------------------------------------------
# the acceptance test: one trace, three tiers, all paired
# ---------------------------------------------------------------------------

class TestThreeTierTrace:
    def test_all_tiers_paired_in_one_export(self, tmp_path):
        jax = pytest.importorskip("jax")
        import numpy as np

        from repro.kernels.matmul.ops import matmul as kernel_mm
        from repro.serving.trace import (TraceConfig, replay_traced,
                                         synthesize_trace)
        from repro.tuner import PlanCache, Tuner, build_default_registry
        from repro.tuner import dispatch

        tr = obs.enable()

        # tier 1: kernel — a real Pallas (interpret-mode) launch timed
        # under kernel_timer with a model prediction attached
        rng = np.random.default_rng(0)
        a = np.asarray(rng.standard_normal((64, 64)), np.float32)
        kt = telemetry.kernel_timer("matmul", (64, 64, 64), {"bm": 32},
                                    predicted={"execute": 1e-4})
        with kt.phase("execute"):
            jax.block_until_ready(kernel_mm(a, a, interpret=True))

        # tier 2: op — a model-guided dispatch (plan predicts the total)
        tuner = Tuner(registry=build_default_registry(),
                      cache=PlanCache(str(tmp_path / "plans")))
        dispatch.matmul(a, a, tuner=tuner)

        # tier 3: serve — cost-model replay steps
        from repro.configs import get
        from repro.core.machine import CPU_HOST
        from repro.serving.cost import cost_model_for
        cost = cost_model_for(get("qwen1.5-4b").reduced(), CPU_HOST)
        trace = synthesize_trace(TraceConfig(n_requests=10, seed=4))
        replay_traced(trace, cost, policy="fifo")

        doc = json.loads(json.dumps(obs.export_spans(tr.spans())))
        by_tier = {"kernel": 0, "op": 0, "serve": 0}
        for ev in _events(doc, "X", 0):
            tier = tier_of(ev.get("cat", ""))
            if tier and "residual_s" in ev.get("args", {}):
                by_tier[tier] += 1
        assert by_tier["kernel"] >= 1, by_tier
        assert by_tier["op"] >= 1, by_tier
        assert by_tier["serve"] >= 1, by_tier
        # every paired measured span has a predicted twin with a flow link
        measured_ids = {ev["args"]["span_id"]
                        for ev in _events(doc, "X", 0)
                        if "residual_s" in ev.get("args", {})}
        twins = {ev["args"].get("pair_of") for ev in _events(doc, "X", 1)}
        assert measured_ids <= twins
        assert len(_events(doc, "s")) == len(_events(doc, "f"))
        assert len(_events(doc, "s")) >= len(measured_ids)

        # and the summary rolls residuals up per tier
        s = obs.summary()
        for tier in ("kernel", "op", "serve"):
            assert s["tiers"][tier]["n_paired"] >= 1
            assert s["tiers"][tier]["mean_rel_err"] is not None
            assert math.isfinite(s["tiers"][tier]["mean_rel_err"])

    def test_disabled_is_inert(self, tmp_path):
        pytest.importorskip("jax")
        import numpy as np

        from repro.tuner import PlanCache, Tuner, build_default_registry
        from repro.tuner import dispatch

        obs.disable()
        rng = np.random.default_rng(0)
        a = np.asarray(rng.standard_normal((64, 64)), np.float32)
        tuner = Tuner(registry=build_default_registry(),
                      cache=PlanCache(str(tmp_path / "plans")))
        out = dispatch.matmul(a, a, tuner=tuner)
        np.testing.assert_allclose(np.asarray(out), a @ a,
                                   rtol=1e-4, atol=1e-4)
        assert obs.tracer().spans() == []
        assert obs.tracer().n_closed == 0
