"""A copy of the benchmark with test-sized cells, for CPU tests.

``make_root(dst)`` copies ``bench/`` beside a link to the program's
``src/`` and adds, to a copy of ``BENCHMARK.json``, the cells
``tiny.mix`` (matmul, TRSM and Cholesky at n = 256), ``tiny.gemm``
(matmul at n = 256 on four devices) and ``tiny.serve``
(the starcoder2 reference architecture at hidden size 128, two layers, a
2048-token vocabulary, in float32, a few requests a second whose prompts
of 128-300 tokens take one or two 128-token chunks and a tail)."""

from __future__ import annotations

import contextlib
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_MODEL = dict(name="sc2-tiny", hidden_size=128, intermediate_size=512,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=32, vocab_size=2048,
                  torch_dtype="float32",
                  serving={"max_cache_len": 512, "max_batch": 4,
                           "policy": "fifo"})
TINY_SERVE = dict(rate_per_s=4.0,
                  prompt={"median": 200, "sigma": 0.5, "min": 128,
                          "max": 300},
                  output={"median": 4, "sigma": 0.5, "min": 2, "max": 6},
                  drain_s=30, check_requests=8, trace_start_s=0.0,
                  trace_seconds=1.0, limits={"served.logit_gap": 1e-3})


def _json(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dst: str, *, model=None, serve=None) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    bm = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = _json(os.path.join(BENCH, "configs", "starcoder2-3b.json"))
    cfg.update(dict(TINY_MODEL, **(model or {})))
    _dump(cfg, os.path.join(dst, "bench", "configs", "sc2-tiny.json"))
    tr = _json(os.path.join(BENCH, "traffic", "code.r80.json"))
    tr.update(dict(TINY_SERVE, **(serve or {})))
    _dump(tr, os.path.join(dst, "bench", "traffic", "tiny.serve.json"))
    mix = _json(os.path.join(BENCH, "traffic", "mix.n16384.json"))
    mix["n"] = 256
    _dump(mix, os.path.join(dst, "bench", "traffic", "tiny.mix.json"))
    gemm = dict(mix, ops=["matmul"],
                limits={"matmul.residual": mix["limits"]["matmul.residual"]})
    _dump(gemm, os.path.join(dst, "bench", "traffic", "tiny.gemm.json"))
    bm["configs"].append({"name": "sc2-tiny", "source": "test",
                          "file": "bench/configs/sc2-tiny.json",
                          "reduced": [], "why": "test"})
    bm["workloads"] += [
        {"name": "tiny.serve", "config": "sc2-tiny", "traffic": "tiny.serve",
         "chips": 1, "why": "test"},
        {"name": "tiny.mix", "config": "linalg-f32", "traffic": "tiny.mix",
         "chips": 1, "why": "test"},
        {"name": "tiny.gemm", "config": "linalg-f32", "traffic": "tiny.gemm",
         "chips": 4, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        ws = m.get("workloads", [])
        if "serve.code.r80" in ws:
            ws.append("tiny.serve")
        if "linalg.mix.n16384" in ws:
            ws.append("tiny.mix")
        if "linalg.gemm.n32768.2x2" in ws:
            ws.append("tiny.gemm")
    _dump(bm, os.path.join(dst, "BENCHMARK.json"))
    return dst


@contextlib.contextmanager
def jax_state():
    """Restore what a run changes in the process: JAX's cache settings,
    the environment."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = dict(os.environ)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        os.environ.clear()
        os.environ.update(env)


def run(root: str, workload: str, *, seed: int = 3000000001,
        seconds: float = 1.0, trace: int = 0, control: int = 0):
    """Run a cell of ``root`` on the CPU; (exit code, result, stderr)."""
    import io
    import time

    from bench import harness
    out, err = io.StringIO(), io.StringIO()
    with jax_state():
        rc = harness.run_cell(
            ["--workload", workload, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace), "--control", str(control)],
            t0=time.perf_counter(),
            root=root, require_tpu=False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def cpu_trace_load(path):
    """``bench.trace.load`` for a trace recorded on the CPU, where the
    operations run on host threads: enough to drive a ``--trace 1`` run's
    reduction and readers in a test."""
    from jax.profiler import ProfileData

    from bench import trace
    pd = ProfileData.from_file(trace.find_xplane(path))
    ops, spans = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                ev = trace.Event(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                if e.name.startswith(trace.SPAN_PREFIX):
                    spans.append(ev)
                elif any(k == "hlo_op" for k, _ in e.stats):
                    ops.append(ev)
    win = [s for s in spans if s.name == trace.WINDOW_SPAN][0]
    lo, hi = win.start, win.end
    return trace.Timeline((lo, hi), [trace.Device(
        "/device:CPU:0", trace._clip(ops, lo, hi), [])], spans)
