"""The program's own host spans in a traced run.

The program writes a span named ``repro.<layer>.<what>`` into the
profiler trace at each layer boundary (``repro.obs.maybe_span``), its
args as TraceMe metadata: ``repro.linalg.<op>`` and ``repro.dispatch.*``
on the linalg path, ``repro.serve.*`` in ``Scheduler.step``.
``bench/trace.py`` keeps the harness's ``bench.*`` spans; this module
reads the ``repro.*`` spans of the same file that lie inside the same
window, with their args.

A per-layer reader gets the run's :class:`~bench.trace.Timeline` and not
its file, so :func:`for_timeline` finds the file among the harness's
work directories (``bench-*`` under the temporary directory) by its
``bench.window`` span, which starts and ends where the Timeline's window
does.  It returns no spans when no file matches or the program wrote
none, so a reader of a program without spans returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import tempfile
from typing import Dict, List, Optional, Tuple

from bench.trace import WINDOW_SPAN

PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class ProgramSpan:
    name: str                  # e.g. "repro.serve.prefill", no metadata
    start: float               # seconds, on the trace's clock
    end: float
    args: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start


def read(path: str) -> Tuple[Optional[Tuple[float, float]],
                             List[ProgramSpan]]:
    """The window of one ``.xplane.pb`` file (None without a
    ``bench.window`` span) and the program spans that lie inside it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    windows, spans = [], []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                start = e.start_ns * 1e-9
                end = (e.start_ns + e.duration_ns) * 1e-9
                if e.name == WINDOW_SPAN:
                    windows.append((start, end))
                elif e.name.startswith(PREFIX):
                    spans.append(ProgramSpan(e.name.split("#", 1)[0], start,
                                             end, dict(e.stats)))
    if not windows:
        return None, []
    lo, hi = windows[0]
    return (lo, hi), sorted((s for s in spans
                             if s.start >= lo and s.end <= hi),
                            key=lambda s: s.start)


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:                    # another run removed its directory
        return 0.0


def for_timeline(tl) -> List[ProgramSpan]:
    """The program spans of the traced run whose window ``tl`` holds;
    the newest matching file under the harness's work directories."""
    return _for_window(tuple(tl.window))


@functools.lru_cache(maxsize=4)       # the readers of one run share it
def _for_window(window: Tuple[float, float]) -> List[ProgramSpan]:
    pattern = os.path.join(tempfile.gettempdir(), "bench-*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True), key=_mtime,
                       reverse=True):
        try:
            found, spans = read(path)
        except Exception:  # noqa: BLE001 - another run's file, mid-write
            continue
        if found == window:
            return spans
    return []


def named(spans: List[ProgramSpan], name: str) -> List[ProgramSpan]:
    return [s for s in spans if s.name == name]
