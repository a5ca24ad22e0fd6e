"""The program's own regions in the traced window: the ``serving.*`` spans
that ``tpu.profiling.annotate`` wrote into the host plane of this run's
xplane, WITH their stats (``trace_reduce.load`` keeps the ``bench.`` spans
and their names only).

A reader gets ``obs`` alone, and ``obs`` does not say where the trace was
written. ``harness.Profiler`` writes to ``.bench_trace/<cell>/``, so the
file is found from there: the newest xplane whose ``bench.window`` span is
``obs["trace_window"]`` (a stale file of another run never matches). A
test, or a later harness, may put the spans into ``obs["serving_spans"]``
itself; that is taken first.

One engine thread writes all ``serving.engine.*`` spans, so on its line a
span's children are the spans that lie inside it.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from benchmark import harness, trace_reduce as tr

PREFIX = "serving."
TURN = "serving.engine.turn"
_loaded: Dict[Tuple[str, int], Tuple[List["Span"], List["Span"]]] = {}


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    line: str                   # the thread's line in the host plane
    stats: Dict[str, Any]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def spans_in_file(path: str) -> Tuple[List[Span], List[Span]]:
    """(``serving.`` spans, ``bench.window`` spans) of one xplane file."""
    key = (path, os.stat(path).st_mtime_ns)      # a rewritten file is another file
    if key not in _loaded:
        from jax.profiler import ProfileData

        ours: List[Span] = []
        windows: List[Span] = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                # a line is one thread; threads share names ("python3")
                thread = f"{line.name}#{i}"
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        ours.append(Span(e.name, e.start_ns, e.duration_ns,
                                         thread, dict(e.stats)))
                    elif e.name == harness.TRACE_SPAN:
                        windows.append(Span(e.name, e.start_ns, e.duration_ns,
                                            thread, {}))
        _loaded[key] = (ours, windows)
    return _loaded[key]


def xplane_of(obs: Dict[str, Any]) -> Optional[str]:
    """This run's xplane file, or None."""
    if "trace_window" not in obs:
        return None
    lo, hi = obs["trace_window"]
    paths = glob.glob(os.path.join(str(harness.ROOT), ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        if any(w.start_ns == lo and w.end_ns == hi for w in spans_in_file(path)[1]):
            return path
    return None


def serving_spans(obs: Dict[str, Any]) -> List[Span]:
    """Every ``serving.*`` span of the traced run, by start."""
    if "serving_spans" in obs:
        return sorted(obs["serving_spans"], key=lambda s: s.start_ns)
    path = xplane_of(obs)
    if path is None:
        return []
    return sorted(spans_in_file(path)[0], key=lambda s: s.start_ns)


def inside(spans: List[Span], window: Tuple[float, float], name: str) -> List[Span]:
    """Spans of that name wholly inside the window."""
    lo, hi = window
    return [s for s in spans if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def children(spans: List[Span], parent: Span, names: Tuple[str, ...]) -> List[Span]:
    """Spans of those names on the parent's line that lie inside it."""
    return [s for s in spans if s.name in names and s.line == parent.line
            and s.start_ns >= parent.start_ns and s.end_ns <= parent.end_ns]


EDGE = "(turn cut by the capture's edge)"


def idle_by_span(trace: tr.Trace, window: Tuple[float, float], spans: List[Span],
                 ) -> List[Tuple[str, float]]:
    """The device's idle seconds inside the window (first device) by the
    innermost (shortest) ``serving.engine.*`` span that covers each idle
    interval's midpoint: what the engine thread was doing while the chip
    waited. A region is recorded only if the session saw it open and
    close, so the turn that was open when the capture began, and the one
    still open when it ended, are missing with their children; idle time
    before the first recorded turn and after the last goes to ``EDGE``.
    ``(no span)`` is idle time between those that no region covers.
    ``trace_reduce.idle_gaps`` does the same over the ``bench.`` spans."""
    if not trace.device_ops:
        return []
    dev = sorted(trace.device_ops)[0]
    busy = tr.union(tr.clip(tr.spans_of(trace.device_ops[dev]), window))
    engine = sorted((s for s in spans if s.name.startswith("serving.engine.")),
                    key=lambda s: s.dur_ns)
    turns = [s for s in engine if s.name == TURN]
    first = min((t.start_ns for t in turns), default=window[1])
    last = max((t.end_ns for t in turns), default=window[1])
    acc: Dict[str, float] = {}
    for s, e in tr.subtract([window], busy):
        mid = (s + e) / 2
        owner = next((h.name for h in engine if h.start_ns <= mid <= h.end_ns), None)
        if owner is None:
            owner = EDGE if mid < first or mid > last else "(no span)"
        acc[owner] = acc.get(owner, 0.0) + (e - s) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])
