"""The reduction from a profiler trace to busy, idle, kernel and
collective time, on synthetic timelines and on a small recorded TPU
trace."""

import glob
import os

import pytest

from bench import trace
from bench.harness import Reading, load_module
from bench.tests.tiny_root import BENCH
from bench.trace import Device, Event, Timeline

MM = ('%matmul.3 = f32[256,256]{1,0} custom-call(f32[256,128]{1,0} %a, '
      'f32[128,256]{1,0} %b), custom_call_target="tpu_custom_call"')
DIAG = ('%trsm.4 = f32[256,128]{1,0} custom-call(f32[128,128]{1,0} %u, '
        'f32[256,128]{1,0} %b), custom_call_target="tpu_custom_call"')
PERMUTE = "%collective-permute-done.1 = f32[8]{0} collective-permute-done()"
FUSION = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)"
DATA = os.path.join(os.path.dirname(__file__), "data")


def timeline():
    # device 0: 0-2 matmul, 3-4 permute (1 s exposed), 4-5 fusion;
    # device 1: 0-1 fusion, 1-3 permute overlapping a 2-3 diag solve
    d0 = Device("/device:TPU:0", [Event(MM, 0, 2), Event(PERMUTE, 3, 4),
                                  Event(FUSION, 4, 5)],
                [Event("jit__summa_body(1)", 0, 5)])
    d1 = Device("/device:TPU:1", [Event(FUSION, 0, 1), Event(PERMUTE, 1, 3),
                                  Event(DIAG, 2, 3)],
                [Event("jit__trsm_body(2)", 0, 3)])
    spans = [Event("bench.window", 0, 10), Event("bench.call.matmul", 0, 6),
             Event("bench.reading", 6, 7), Event("bench.wait", 7, 10)]
    return Timeline((0.0, 10.0), [d0, d1], spans)


def test_union_and_measure():
    assert trace.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5),
                                                               (3, 4)]
    merged = [(0, 2.5), (3, 4)]
    assert trace.measure(merged, 2, 3.5) == pytest.approx(1.0)
    assert trace.measure(merged, 5, 6) == 0.0


def test_busy_idle_and_gaps_by_host_span():
    tl = timeline()
    assert tl.window_s == 10.0
    assert tl.busy_s() == pytest.approx((4.0 + 3.0) / 2)
    assert tl.idle_share() == pytest.approx(1 - 3.5 / 10)
    assert tl.idle_gaps(tl.devices[0]) == [(2, 3), (5, 10)]
    idle = tl.idle_by_span()
    # device 0: 2-3 in the call, 5-10 mostly waiting (middle 7.5);
    # device 1: 3-10 (middle 6.5, the reading)
    assert idle == {"bench.call.matmul": 0.5, "bench.wait": 2.5,
                    "bench.reading": 3.5}
    bd = tl.breakdown(top=2)
    assert bd["device_ops"][0][0] in ("matmul [pallas]",
                                      "collective-permute-done")
    assert len(bd["device_ops"]) == 2 and len(bd["idle_gaps"]) == 2


def test_self_time_of_nested_operations():
    loop = Event("%while.1 = f32[8]{0} while(f32[8]{0} %p)", 0.0, 10.0)
    body = [Event(FUSION, 1.0, 3.0), Event(MM, 4.0, 8.0)]
    tl = Timeline((0.0, 10.0), [Device("d", [loop] + body, [])], [])
    times = tl.op_time_by_name()
    assert times["while"] == pytest.approx(4.0)
    assert times["matmul [pallas]"] == pytest.approx(4.0)
    assert sum(times.values()) == pytest.approx(tl.busy_s())


def test_collective_exposed_and_names():
    tl = timeline()
    assert trace.op_base(PERMUTE) == "collective-permute-done"
    assert trace.is_collective(Event(PERMUTE, 0, 1))
    assert not trace.is_collective(Event(MM, 0, 1))
    assert trace.is_pallas(Event(MM, 0, 1))
    assert trace.op_base("jit__summa_body(6899914246217340726)") == \
        "jit__summa_body"
    assert tl.devices[0].collective_exposed_s() == pytest.approx(1.0)
    assert tl.devices[1].collective_exposed_s() == pytest.approx(1.0)


def reading(tl, layer):
    return Reading(tl, layer, len(tl.devices),
                   {"bf16_flops": 197e12, "hbm_bytes_s": 819e9})


def metric(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_readers_on_a_synthetic_timeline():
    tl = timeline()
    tiles = {"matmul": {"matmul": {"bm": 128, "bn": 128, "bk": 128}},
             "trsm": {"matmul": {"bm": 128, "bn": 128, "bk": 128}}}
    r = reading(tl, {"tiles": tiles})
    assert metric("collective.exposed_share").read(r) == pytest.approx(10.0)
    assert metric("kernel.diag_share").read(r) == pytest.approx(100 / 7)
    assert metric("device_idle.linalg").read(r) == pytest.approx(65.0)
    flops = 2 * 256 * 128 * 256
    nbytes = 4 * (256 * 128 * 2 + 128 * 256 * 2 + 256 * 256)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 2.0
    assert metric("kernel.matmul_roofline").read(r) == pytest.approx(want)
    # nothing to read: no value, never a 0 share
    empty = reading(Timeline((0, 1), [Device("d", [], [])], []), {})
    for name in ("collective.exposed_share", "kernel.diag_share",
                 "kernel.matmul_roofline", "model.decode_step_ms",
                 "sched.step_host_ms", "linalg_mfu", "serve_mfu"):
        assert metric(name).read(empty) is None, name


def test_host_clock_readers():
    calls = [{"op": "matmul", "flops": 197e12, "wall_s": 2.0,
              "predicted_s": 1.0, "traced": True},
             {"op": "matmul", "flops": 197e12, "wall_s": 4.0,
              "predicted_s": 4.0 * 2.718281828459045 ** 2, "traced": True},
             {"op": "matmul", "flops": 1.0, "wall_s": 1.0,
              "predicted_s": 1.0, "traced": False}]
    r = reading(timeline(), {"calls": calls})
    assert metric("linalg_mfu").read(r) == pytest.approx(100 * 2 / 6 / 2)
    err = metric("planner.model_err").read(r)
    assert err == pytest.approx((0.6931471805599453 * 2.0) ** 0.5)
    steps = [{"flops": 197e12, "wall_s": 4.0, "prefill_tokens": 100,
              "traced": True}]
    assert metric("serve_mfu").read(reading(timeline(), {"steps": steps})) \
        == pytest.approx(100 / 4 / 2)


def test_serving_readers():
    d = Device("/device:TPU:0",
               [Event(FUSION, 0.0, 0.4), Event(FUSION, 1.0, 1.1)],
               [Event("jit_serve_step(5)", 0.0, 0.4),
                Event("jit_step(7)", 1.0, 1.1)])
    tl = Timeline((0.0, 2.0), [d], [Event("bench.step", 0.0, 0.5),
                                    Event("bench.step", 0.9, 1.3)])
    r = reading(tl, {"steps": [{"prefill_tokens": 400, "traced": True}]})
    assert metric("model.decode_step_ms").read(r) == pytest.approx(100.0)
    assert metric("model.prefill_ms_per_ktok").read(r) == pytest.approx(1000.0)
    assert metric("sched.step_host_ms").read(r) == pytest.approx(
        1e3 * (0.1 + 0.3) / 2)
    assert metric("device_idle.serve").read(r) == pytest.approx(75.0)


def test_recorded_tpu_trace():
    """A short window recorded on one TPU v5e: a Pallas matmul call and a
    TRSM call under the harness's spans."""
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert path, "the recorded trace is committed beside this test"
    tl = trace.load(path[0])
    assert len(tl.devices) == 1
    assert 0 < tl.busy_s() < tl.window_s
    names = {trace.op_base(o.name) for o in tl.devices[0].ops
             if trace.is_pallas(o)}
    assert {"matmul", "trsm"} <= names
    assert {s.name for s in tl.spans} >= {"bench.call.matmul",
                                          "bench.call.trsm"}
    idle = tl.idle_by_span()
    assert sum(idle.values()) == pytest.approx(tl.window_s - tl.busy_s())
    tiles = {"matmul": {"matmul": {"bm": 512, "bn": 1024, "bk": 256}},
             "trsm": {"matmul": {"bm": 512, "bn": 1024, "bk": 256}}}
    share = metric("kernel.matmul_roofline").read(reading(tl,
                                                          {"tiles": tiles}))
    assert 0 < share < 100
    assert 0 < metric("kernel.diag_share").read(reading(tl, {})) < 100
