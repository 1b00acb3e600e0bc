"""The program's own spans in a profiler trace, and the readers of them.

A real ``jax.profiler`` trace, recorded on the CPU, of a few serving
steps (prefills and a batched decode) and one model-guided matmul, each
inside the harness's ``bench.*`` spans: every span the program writes is
there, nested in its caller, with its args; the readers of
``sched.queue_s``, ``sched.decode_host_ms`` and ``dispatch.host_ms`` read
finite values from it, and the harness's traced tiny cells report them.
The profiler is process-global, so every test that records one is in
this file.
"""

import glob
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from bench import program_spans, trace
from bench.harness import Reading, load_module
from bench.tests.tiny_root import BENCH, cpu_trace_load, make_root, run

PROMPTS = (20, 11, 9)        # chunk 8: chunks and one-token tails
NEW_TOKENS = 3
DATA = os.path.join(os.path.dirname(__file__), "data")


def _backend():
    from repro.configs import get
    from repro.models import build_model
    from repro.serving.scheduler import ModelBackend
    model = build_model(get("starcoder2-3b").reduced())
    params = model.init(jax.random.PRNGKey(0))
    return ModelBackend(model, params, max_cache_len=64, prefill_chunk=8)


def _scheduler(backend):
    from repro.configs import get
    from repro.core.machine import CPU_HOST
    from repro.serving.cost import cost_model_for
    from repro.serving.policy import FIFOPolicy
    from repro.serving.scheduler import Scheduler, SchedulerConfig
    return Scheduler(backend, cost_model_for(get("starcoder2-3b").reduced(),
                                             CPU_HOST),
                     SchedulerConfig(max_cache_len=64, max_batch=4),
                     policy=FIFOPolicy())


def _serve(backend, tag):
    from repro.serving.scheduler import Request
    sched = _scheduler(backend)
    for k, n in enumerate(PROMPTS):
        with TraceAnnotation("bench.submit"):
            sched.submit(Request(rid=f"{tag}{k}",
                                 prompt=jnp.full((1, n), k + 1, jnp.int32),
                                 max_new_tokens=NEW_TOKENS))
    while not sched.idle:
        with TraceAnnotation("bench.step"):
            sched.step()
    return sched


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace directory's parent, Timeline, program spans) of one window:
    three requests served and one 64 x 64 matmul, compiled before it."""
    from repro.tuner import PlanCache, Tuner
    from repro.tuner import dispatch

    tmp = tmp_path_factory.mktemp("tmp")
    trace_dir = tmp / "bench-spans" / "trace"
    backend = _backend()
    tuner = Tuner(cache=PlanCache(str(tmp / "plans")))
    a = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                    jnp.float32)
    _serve(backend, "warm")
    jax.block_until_ready(dispatch.matmul(a, a, tuner=tuner))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with TraceAnnotation(trace.WINDOW_SPAN):
            _serve(backend, "r")
            with TraceAnnotation("bench.call.matmul"):
                out = dispatch.matmul(a, a, tuner=tuner)
            jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    tl = cpu_trace_load(str(trace_dir))
    _, spans = program_spans.read(trace.find_xplane(str(trace_dir)))
    return str(tmp), tl, spans


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def _parent(span, candidates):
    return [c for c in candidates if _inside(span, c)]


TABLE = {
    "repro.linalg.matmul": {"n"},
    "repro.dispatch.plan": {"op", "n"},
    "repro.dispatch.distribute": {"algo"},
    "repro.dispatch.execute": {"algo", "variant", "g", "c"},
    "repro.serve.step": {"step", "prefill_tokens", "decode_batch"},
    "repro.serve.admit": {"admitted", "waiting"},
    "repro.serve.compose": set(),
    "repro.serve.prefill": {"rid", "tokens", "calls"},
    "repro.serve.decode.stack": {"batch", "padded"},
    "repro.serve.decode.step": {"padded"},
    "repro.serve.decode.unstack": {"batch"},
    "repro.serve.sample": {"batch"},
}


def test_every_span_is_there_with_its_args(recorded):
    _, _, spans = recorded
    for name, keys in TABLE.items():
        found = program_spans.named(spans, name)
        assert found, name
        assert all(keys <= set(s.args) for s in found), (name, found[0])
        assert all("#" not in s.name for s in found)
    plan = program_spans.named(spans, "repro.dispatch.plan")[0]
    assert plan.args["op"] == "matmul" and plan.args["n"] == 64
    # one prefill per request, each whole prompt in its own step; the
    # first (and only) prefill of each carries its queue wait
    prefill = program_spans.named(spans, "repro.serve.prefill")
    assert sorted(s.args["rid"] for s in prefill) == ["r0", "r1", "r2"]
    assert sorted(s.args["tokens"] for s in prefill) == sorted(PROMPTS)
    # calls: whole chunks of 8, then one per token
    assert {s.args["tokens"]: s.args["calls"] for s in prefill} == \
        {20: 2 + 4, 11: 1 + 3, 9: 1 + 1}
    queued = [s.args["queued_s"] for s in prefill]
    assert all(isinstance(q, float) and 0.0 <= q < 60.0 for q in queued)
    # requests queue behind the earlier ones' prefills
    by_rid = {s.args["rid"]: s.args["queued_s"] for s in prefill}
    assert by_rid["r0"] < by_rid["r1"] < by_rid["r2"]
    stack = program_spans.named(spans, "repro.serve.decode.stack")
    assert stack[0].args == {"batch": 3, "padded": 4}
    steps = program_spans.named(spans, "repro.serve.step")
    assert sum(s.args["prefill_tokens"] for s in steps) == sum(PROMPTS)
    assert max(s.args["decode_batch"] for s in steps) == 3


def test_every_span_nests_in_its_caller(recorded):
    _, tl, spans = recorded
    calls = tl.spans_named("bench.call.matmul")
    steps = tl.spans_named("bench.step")
    (entry,) = program_spans.named(spans, "repro.linalg.matmul")
    assert _parent(entry, calls)
    for name in ("repro.dispatch.plan", "repro.dispatch.distribute",
                 "repro.dispatch.execute"):
        (s,) = program_spans.named(spans, name)
        assert _inside(s, entry), name
    roots = program_spans.named(spans, "repro.serve.step")
    assert roots and all(_parent(s, steps) for s in roots)
    for name in TABLE:
        if name.startswith("repro.serve.") and name != "repro.serve.step":
            for s in program_spans.named(spans, name):
                assert _parent(s, roots), name
    # the decode phases run in order inside one step
    order = [program_spans.named(spans, f"repro.serve.{n}")[0]
             for n in ("decode.stack", "decode.step", "decode.unstack",
                       "sample")]
    assert all(a.end <= b.start for a, b in zip(order, order[1:]))


def metric(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


NEW = ("sched.queue_s", "sched.decode_host_ms", "dispatch.host_ms")


def test_new_readers_read_the_recorded_trace(recorded, monkeypatch):
    tmp, tl, spans = recorded
    monkeypatch.setattr(tempfile, "tempdir", tmp)
    program_spans._for_window.cache_clear()
    assert program_spans.for_timeline(tl) == spans
    r = Reading(tl, {}, 1, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
    values = {name: metric(name).read(r) for name in NEW}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in values.values()), values
    prefill = program_spans.named(spans, "repro.serve.prefill")
    assert values["sched.queue_s"] == pytest.approx(
        sum(s.args["queued_s"] for s in prefill) / len(prefill))
    (entry,) = program_spans.named(spans, "repro.linalg.matmul")
    assert values["dispatch.host_ms"] == pytest.approx(1e3 * entry.dur)


def test_new_readers_without_program_spans():
    """A trace with no program span, as a parent without them records:
    no value, and no error."""
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    window, spans = program_spans.read(path)
    assert window is not None and spans == []
    tl = trace.load(path)
    r = Reading(tl, {}, 1, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
    for name in NEW:
        assert metric(name).read(r) is None, name
        assert metric(name).read(Reading(None, {}, 1, {})) is None, name


#: what each accepted reader reads on the recorded v5e trace, with the
#: tiles of the run that recorded it (values before program spans were
#: written; nothing here may move them)
RECORDED = {
    "collective.exposed_share": None,
    "device_idle.linalg": 99.32074900135382,
    "device_idle.serve": 99.32074900135382,
    "kernel.diag_share": 79.42211719915733,
    "kernel.matmul_roofline": 46.60445597816297,
    "linalg_mfu": None,
    "model.decode_step_ms": None,
    "model.prefill_ms_per_ktok": None,
    "planner.model_err": None,
    "sched.step_host_ms": None,
    "serve.itl_p99_s": None,
    "serve_mfu": None,
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_accepted_readers_unchanged_on_recorded_trace(name):
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    tiles = {"matmul": {"matmul": {"bm": 512, "bn": 1024, "bk": 256}},
             "trsm": {"matmul": {"bm": 512, "bn": 1024, "bk": 256}}}
    r = Reading(trace.load(path), {"tiles": tiles}, 1,
                {"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
    assert metric(name).read(r) == RECORDED[name]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


@pytest.mark.parametrize("cell,names", [
    ("tiny.serve", ("sched.queue_s", "sched.decode_host_ms")),
    ("tiny.mix", ("dispatch.host_ms",))])
def test_traced_tiny_cell_reports_new_metrics(root, monkeypatch, cell,
                                              names):
    """Through the harness: the readers find the run's own trace."""
    monkeypatch.setattr(trace, "load", cpu_trace_load)
    rc, res, err = run(root, cell, seconds=2.0, trace=1)
    assert rc == 0 and res["correct"], err
    for name in names:
        v = res["metrics"][name]["value"]
        assert math.isfinite(v) and v > 0, (name, v)
    others = set(NEW) - set(names)
    assert not others & set(res["metrics"])


def test_serve_program_module_names():
    """The decode and prefill programs keep the module names the serving
    readers match in a trace (``jit_step``, ``jit_serve_step``)."""
    backend = _backend()
    model = backend.model
    caches = model.init_cache(1, backend.max_cache_len)
    tok = jnp.zeros((1, 1), jnp.int32)
    prefill = backend._step.lower(backend.params, jnp.zeros((1, 8), jnp.int32),
                                  caches, None).as_text()
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), caches)
    decode = backend._vstep().lower(backend.params, jnp.stack([tok, tok]),
                                    stacked).as_text()
    assert prefill.startswith("module @jit_serve_step")
    assert decode.startswith("module @jit_step")


# -- with the program's own recording on -------------------------------------

@pytest.fixture
def recording():
    from repro import obs
    obs.reset()
    tr = obs.enable()
    yield tr
    obs.reset()


def test_recorded_dispatch_has_one_span_per_phase_and_never_blocks(
        recording, tmp_path, monkeypatch):
    """Recording on, telemetry off: one tracer span per region, the
    execute span paired with the plan's prediction, and nothing blocks
    on the result."""
    from repro.tuner import PlanCache, Tuner
    from repro.tuner import dispatch

    def no_block(x):
        raise AssertionError("a span blocked on the result")

    a = jnp.asarray(np.eye(32, dtype=np.float32))
    tuner = Tuner(cache=PlanCache(str(tmp_path / "plans")))
    monkeypatch.setattr(dispatch.jax, "block_until_ready", no_block)
    out = dispatch.matmul(a, a, tuner=tuner)
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(out), np.eye(32), atol=1e-6)
    names = [sp.name for sp in recording.spans()]
    assert sorted(names) == ["dispatch.distribute", "dispatch.execute",
                             "dispatch.plan", "linalg.matmul"]
    by = {sp.name: sp for sp in recording.spans()}
    assert by["dispatch.execute"].predicted_s is not None
    assert by["dispatch.execute"].cat == "dispatch"
    root = by["linalg.matmul"]
    assert all(by[n].trace_id == root.span_id for n in by)


def test_recorded_scheduler_step_spans(recording):
    """Admit and compose are real tracer spans under the step root, whose
    duration stays on the scheduler's clock."""
    sched = _serve(_backend(), "o")
    spans = recording.spans()
    roots = {sp.span_id: sp for sp in spans if sp.name == "serve:step"}
    admits = [sp for sp in spans if sp.name == "serve.admit"]
    assert admits and all(sp.parent_id in roots for sp in admits)
    assert all({"admitted", "waiting"} <= set(sp.args) for sp in admits)
    assert any(sp.name == "serve.compose" for sp in spans)
    assert any(sp.name == "serve.prefill" and "queued_s" in sp.args
               for sp in spans)
    assert sched.steps == len([r for r in roots.values()
                               if not r.args.get("fast_forward")])
