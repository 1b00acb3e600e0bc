"""One run of one cell: find its files by name, set up, measure, check,
print the result line.

The cell's entry in ``BENCHMARK.json`` names its configuration and its
traffic mix.  The configuration's file (``configs/<name>.json``) holds the
sizes as they are run and names its plain reference beside it; the
traffic file (``traffic/<traffic>.json``) holds the mix's parameters and
names the ``kind`` whose module (``kinds/<kind>.py``) generates the
requests and drives the program.  Per-layer metrics are read, after a
``--trace 1`` run, by ``metrics/<name>.py``.  Adding a cell, a mix, a
kind or a metric adds files and entries; no file here changes.

A kind module has ``make(ctx)``, returning an object with:

* ``setup()``: build operands or weights on the device from the seed and
  warm up every shape the window uses;
* ``window(seconds)``: the measured window (``ctx.trace_begin()`` and
  ``ctx.trace_end()`` mark the part a ``--trace 1`` run records);
* ``finish()``: let the work due in the window end, up to a limit;
* ``release()``: free the program's state before the reference runs;
* ``checks()``: the comparisons that decide ``correct``;
* ``attempted``, ``failed``, ``end_to_end()`` and ``layer`` (what the
  per-layer readers read).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import logging
import math
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: JAX's persistent compile cache, at a fixed path inside the checkout.
CACHE_SUBDIR = os.path.join("bench", ".cache", "jax")

#: Recording switches of the program, kept off in every run.
PROGRAM_RECORDING = ("REPRO_TELEMETRY", "REPRO_OBS")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class NoChip(RuntimeError):
    """The host lacks the accelerator or the chip count the cell asks for."""


# -- finding files by name ------------------------------------------------------

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named_file(root: str, subdir: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    path = os.path.join(root, "bench", subdir, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{subdir} {name!r}: no file {path}")
    return path


def traffic_file(root: str, traffic: str) -> str:
    return _named_file(root, "traffic", traffic, ".json")


def kind_file(root: str, kind: str) -> str:
    return _named_file(root, "kinds", kind, ".py")


def metric_file(root: str, metric: str) -> str:
    return _named_file(root, "metrics", metric, ".py")


def load_module(path: str):
    """Import a file of the benchmark by its path."""
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR)
                             if path.startswith(BENCH_DIR) else path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entry(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def config_entry(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def reference_module(root: str, cfg_entry: dict, config: dict):
    """The plain reference the configuration's file names, beside it."""
    here = os.path.dirname(os.path.join(root, cfg_entry["file"]))
    return load_module(os.path.join(here, config["reference"]))


def cell_metrics(bm: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


# -- seeds ---------------------------------------------------------------------

def seed_key(seed: int):
    """A JAX key from any whole seed, wider than 32 bits included."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def seed_rng(seed: int, stream: int = 0):
    import numpy as np
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


# -- a run ---------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit; the check holds iff the value
    is finite and at most the limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Context:
    """What a kind sees of the run: arguments, files, devices, spans."""

    def __init__(self, *, args, config, traffic, reference, devices,
                 work_dir):
        self.seed = args.seed
        self.config = config
        self.traffic = traffic
        self.reference = reference
        self.devices = devices
        self.work_dir = work_dir
        self.tracing = bool(args.trace)
        self.control = bool(getattr(args, "control", 0))
        self.trace_dir = os.path.join(work_dir, "trace")
        self.trace_host = (None, None)     # host clock of begin / end
        self.trace_pause_s = 0.0           # stop_trace's own time
        self._window_span = None

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def trace_begin(self) -> None:
        if not self.tracing or self._window_span is not None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window_span = self.span("bench.window")
        self._window_span.__enter__()
        self.trace_host = (time.perf_counter(), None)

    def trace_end(self) -> None:
        if self._window_span is None or self.trace_host[1] is not None:
            return
        import jax
        t = time.perf_counter()
        self.trace_host = (self.trace_host[0], t)
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.trace_pause_s = time.perf_counter() - t

    def in_trace(self, t: float) -> bool:
        lo, hi = self.trace_host
        return lo is not None and hi is not None and lo <= t <= hi


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets."""
    timeline: Any                    # trace.Timeline, or None
    layer: dict                      # the kind's own records
    chips: int
    peaks: dict


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: the configuration's reference, one precision "
                    "below the stated one, takes the program's place; such "
                    "a run must come out not correct")
    return ap.parse_args(argv)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    """Point JAX's persistent cache at the fixed directory in the checkout,
    for every program however short its compile."""
    import jax
    path = os.path.join(root, CACHE_SUBDIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileLog(logging.Handler):
    """Names of the programs JAX lowers while it is installed (with
    ``jax_log_compiles`` on): nothing should compile in the window."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.names: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with ")[0][len("Compiling "):])

    @contextlib.contextmanager
    def watching(self):
        import jax
        logger = logging.getLogger("jax")
        was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logger.addHandler(self)
        try:
            yield self
        finally:
            logger.removeHandler(self)
            jax.config.update("jax_log_compiles", was)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _fmt(x: float) -> str:
    return repr(float(x))


@dataclasses.dataclass
class Cell:
    """One cell's files, loaded by name, and the devices it runs on."""
    entry: dict
    ctx: Context
    kind: Any
    e2e: list
    layer_metrics: list
    peaks: dict


def open_cell(args, work_dir: str, *, root: str = ROOT,
              require_tpu: bool = True) -> Cell:
    """Load the cell's files and claim its devices (raises NoChip), with
    the compile cache on and the program's own recording off."""
    for var in PROGRAM_RECORDING:
        os.environ.pop(var, None)
    bm = load_benchmark(root)
    cell = cell_entry(bm, args.workload)
    cfg_entry = config_entry(bm, cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(traffic_file(root, cell["traffic"]))
    kind = load_module(kind_file(root, traffic["kind"]))
    e2e, layer_metrics = cell_metrics(bm, cell["name"])
    devices = _devices(int(cell["chips"]), require_tpu)
    from . import peaks as peaks_mod
    peaks = peaks_mod.peaks_for(devices[0].device_kind) if require_tpu \
        else peaks_mod.PEAKS["TPU v5 lite"]
    enable_compile_cache(root)
    os.environ["REPRO_PLAN_DIR"] = os.path.join(work_dir, "plans")
    from repro import obs, telemetry
    if obs.enabled() or telemetry.enabled():
        raise RuntimeError("the program's own recording is on")
    ctx = Context(args=args, config=config, traffic=traffic,
                  reference=reference_module(root, cfg_entry, config),
                  devices=devices, work_dir=work_dir)
    return Cell(cell, ctx, kind, e2e, layer_metrics, peaks)


def run_cell(argv=None, *, t0: float, root: str = ROOT,
             require_tpu: bool = True, out=None, err=None) -> int:
    """Run one cell once; print the result as the last line of ``out``.
    Returns the process exit code."""
    out = out or sys.stdout
    err = err or sys.stderr
    args = parse_args(argv)
    work_dir = tempfile.mkdtemp(prefix="bench-")
    try:
        try:
            cell = open_cell(args, work_dir, root=root,
                             require_tpu=require_tpu)
        except NoChip as e:
            print(f"bench: {e}; nothing was run", file=err)
            return 3
        ctx, devices = cell.ctx, cell.ctx.devices
        readers = {m["name"]: load_module(metric_file(root, m["name"]))
                   for m in cell.layer_metrics} if args.trace else {}
        job = cell.kind.make(ctx)
        job.setup()
        setup_s = time.perf_counter() - t0
        t_w = time.perf_counter()
        with CompileLog().watching() as compiled:
            job.window(args.seconds)
            ctx.trace_end()
        window_s = time.perf_counter() - t_w
        job.finish()
        peak = memory_peak(devices)
        job.release()
        checks: List[Check] = job.checks()
        correct = bool(checks) and all(c.ok for c in checks)

        result: Dict[str, Any] = {
            "correct": correct, "attempted": int(job.attempted),
            "failed": int(job.failed)}
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        if args.trace:
            from . import trace as trace_mod
            tl = trace_mod.load(ctx.trace_dir)
            reading = Reading(tl, job.layer, len(devices), cell.peaks)
            metrics = {}
            for m in cell.layer_metrics:
                v = readers[m["name"]].read(reading)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            device.update(busy_s=tl.busy_s(), window_s=tl.window_s)
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = tl.breakdown()
        else:
            values = job.end_to_end()
            values["setup_s"] = setup_s
            result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                             "unit": m["unit"]}
                                 for m in cell.e2e}
            result["device"] = device
        result["window"] = {"seconds": window_s, "setup_s": setup_s,
                            "compiled_in_window": compiled.names}
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in checks}
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    for c in checks:
        print(f"check {c.name} value {_fmt(c.value)} limit {_fmt(c.limit)} "
              f"{'ok' if c.ok else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
