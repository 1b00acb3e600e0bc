"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A run with ``--trace 1`` records one window with ``jax.profiler`` and
marks it with a host span named ``bench.window``; the harness writes its
own ``bench.*`` spans (``jax.profiler.TraceAnnotation``) around each call
into the program.  :func:`load` reads the ``.xplane.pb`` file with
``jax.profiler.ProfileData`` and keeps, clipped to the window:

* per device (planes ``/device:TPU:<i>``), the operations of the ``XLA
  Ops`` line and the programs of the ``XLA Modules`` line, each as
  (name, start, end) in seconds on the trace's clock;
* the host's ``bench.*`` spans from every thread of ``/host:CPU``.

Busy time is the union of the intervals in which an operation ran; an
idle gap is a stretch of the window with none, put down to the innermost
``bench.*`` span around its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "outside bench spans"

_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|send|recv|"
                         r"collective-broadcast)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str          # full name as traced (an op's HLO text)
    start: float       # seconds
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def op_base(name: str) -> str:
    """``%matmul.12 = f32[...] custom-call(...)`` -> ``matmul``;
    ``jit_step(123)`` -> ``jit_step``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"\(\d+\)$", "", head)
    return re.sub(r"\.\d+$", "", head)


def is_pallas(op: Event) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op.name


def is_collective(op: Event) -> bool:
    return bool(_COLLECTIVE.match(op_base(op.name)))


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration less that of the events nested
    directly inside it (events of one line nest or do not overlap)."""
    out: List[list] = []
    stack: List[list] = []
    for o in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= o.start:
            stack.pop()
        entry = [o, o.dur]
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] -= o.dur
        out.append(entry)
        stack.append(entry)
    return [(o, max(0.0, t)) for o, t in out]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged: Sequence[Tuple[float, float]], lo: float,
            hi: float, starts: Optional[Sequence[float]] = None) -> float:
    """Length of ``merged`` (sorted, disjoint) inside [lo, hi];
    ``starts`` are the intervals' starts, when the caller keeps them."""
    if hi <= lo or not merged:
        return 0.0
    if starts is None:
        starts = [s for s, _ in merged]
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]
    _busy: Optional[List[Tuple[float, float]]] = None
    _starts: Optional[List[float]] = None

    @property
    def busy_intervals(self) -> List[Tuple[float, float]]:
        if self._busy is None:
            self._busy = union([(o.start, o.end) for o in self.ops])
            self._starts = [s for s, _ in self._busy]
        return self._busy

    def busy_s(self, lo: Optional[float] = None,
               hi: Optional[float] = None) -> float:
        b = self.busy_intervals
        if lo is None:
            return sum(e - s for s, e in b)
        return measure(b, lo, hi, self._starts)

    def op_time(self, pred: Callable[[Event], bool]) -> float:
        return sum(o.dur for o in self.ops if pred(o))

    def collective_exposed_s(self) -> float:
        """Collective time during which no other operation runs here."""
        compute = union([(o.start, o.end) for o in self.ops
                         if not is_collective(o)])
        coll = union([(o.start, o.end) for o in self.ops if is_collective(o)])
        return sum((e - s) - measure(compute, s, e) for s, e in coll)


@dataclasses.dataclass
class Timeline:
    window: Tuple[float, float]
    devices: List[Device]
    spans: List[Event]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        return sum(d.busy_s() for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]

    def idle_gaps(self, device: Device) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in device.busy_intervals:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def __post_init__(self):
        self.spans = sorted((s for s in self.spans if s.name != WINDOW_SPAN),
                            key=lambda s: s.start)
        self._starts = [s.start for s in self.spans]

    def span_at(self, t: float) -> str:
        """The innermost (latest-starting) bench span around time t."""
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0:
            if self.spans[i].end > t:
                return self.spans[i].name
            i -= 1
        return NO_SPAN

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds by what the host was doing, averaged over the
        devices."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for s, e in self.idle_gaps(d):
                out[self.span_at(0.5 * (s + e))] += (e - s) / len(self.devices)
        return dict(out)

    def op_time_by_name(self) -> Dict[str, float]:
        """Device self time by operation name (the HLO name without its
        numeric suffix; Pallas kernels marked), averaged over devices.  An
        operation that encloses others, as a ``while`` its body, counts
        only the time none of them runs."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for o, self_s in self_times(d.ops):
                name = op_base(o.name) + (" [pallas]" if is_pallas(o) else "")
                out[name] += self_s / len(self.devices)
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        def head(dct):
            return [[k, v] for k, v in sorted(dct.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_time_by_name()),
                "idle_gaps": head(self.idle_by_span())}


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Timeline:
    """Read one trace file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            evs = {}
            for key in ("XLA Ops", "XLA Modules"):
                ln = lines.get(key)
                evs[key] = [] if ln is None else [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in ln.events]
            devices.append((int(plane.name.rsplit(":", 1)[1]),
                            plane.name, evs["XLA Ops"], evs["XLA Modules"]))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns)
                                           * 1e-9))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace {path} holds no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"trace {path} holds no TPU device plane")
    lo, hi = windows[0].start, windows[0].end
    devs = [Device(name, _clip(ops, lo, hi), _clip(mods, lo, hi))
            for _, name, ops, mods in sorted(devices)]
    spans = [s for s in spans if s.end > lo and s.start < hi]
    return Timeline((lo, hi), devs, spans)
