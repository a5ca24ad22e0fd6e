"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What a
TPU trace of this JAX looks like (seen by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per program
execution, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO
operation, named by the operation's whole HLO text; ``while`` bodies nest
inside their ``while`` event) and ``Async XLA Ops``; and a ``/host:CPU``
plane whose ``python`` line carries ``TraceAnnotation`` spans. Device and
host events are on one clock, in nanoseconds.

Everything here works on plain ``Event`` tuples, so the self-test can feed
it hand-made events as well as the small recorded trace beside it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Interval = Tuple[float, float]

CONTAINER_OPS = ("while", "conditional", "call")
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute", "collective-broadcast")


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    """Per device: ops and module executions. Host: span name -> events."""
    device_ops: Dict[str, List[Event]]
    device_async: Dict[str, List[Event]]
    device_modules: Dict[str, List[Event]]
    host_spans: List[Event]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, host_prefix: str = "bench.") -> Trace:
    """Device lines as they are; of the host's threads, the spans whose name
    starts with ``host_prefix`` (the benchmark's own ``TraceAnnotation``s,
    whichever thread made them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    asyncs: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                target = {"XLA Ops": ops, "Async XLA Ops": asyncs,
                          "XLA Modules": modules}.get(line.name)
                if target is None:
                    continue
                target[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name.startswith(host_prefix))
    return Trace(ops, asyncs, modules, host)


# -- names -------------------------------------------------------------------

_HLO = re.compile(r"^%?([\w.\-]+) = .*? ([\w\-]+)\(")


def op_kind(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``custom-call``,
    ``while``, ``all-gather-start``...), or the name itself when it is not
    HLO text."""
    m = _HLO.match(name)
    return m.group(2) if m else name


def short_name(name: str) -> str:
    """``%attention.40 = (...) custom-call(...)`` -> ``attention.40
    custom-call``: what a breakdown prints."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def is_container(name: str) -> bool:
    return op_kind(name) in CONTAINER_OPS


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind == c or kind.startswith(c + "-") for c in COLLECTIVE_OPS)


def is_pallas_call(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def operand_count(name: str) -> int:
    """Operands of an HLO op, counted as ``%name`` references inside the
    opcode's parentheses."""
    m = _HLO.match(name)
    if not m:
        return 0
    depth, start = 0, m.end() - 1
    for j in range(start, len(name)):
        if name[j] == "(":
            depth += 1
        elif name[j] == ")":
            depth -= 1
            if depth == 0:
                return name[start:j].count(" %") + name[start:j].count("(%")
    return 0


# -- intervals ---------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by the
    (disjoint, sorted) intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


# -- reductions --------------------------------------------------------------

def window_of(trace: Trace, span: str) -> Interval:
    """The traced window: the host span of that name (the longest one)."""
    hits = [e for e in trace.host_spans if e.name == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    e = max(hits, key=lambda e: e.dur_ns)
    return (e.start_ns, e.end_ns)


def busy_by_device(trace: Trace, window: Interval) -> Dict[str, float]:
    """Seconds, per device, in which any operation ran inside the window:
    the union of the ``XLA Ops`` intervals (nested ``while`` bodies and all)
    clipped to the window."""
    return {dev: total(union(clip(spans_of(evs), window))) / 1e9
            for dev, evs in trace.device_ops.items()}


def op_seconds(trace: Trace, window: Interval, top: int = 10,
               ) -> List[Tuple[str, float]]:
    """Device operations by total time inside the window, summed over
    devices and executions, container ops (``while``...) left out since
    their bodies are listed themselves."""
    acc: Dict[str, float] = {}
    for evs in trace.device_ops.values():
        for e in evs:
            if is_container(e.name):
                continue
            for s, t in clip([(e.start_ns, e.end_ns)], window):
                key = short_name(e.name)
                acc[key] = acc.get(key, 0.0) + (t - s) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def kernel_events(trace: Trace, window: Interval, pred) -> List[Event]:
    """Events on any device's ``XLA Ops`` line, wholly inside the window,
    whose name satisfies ``pred``."""
    lo, hi = window
    return [e for evs in trace.device_ops.values() for e in evs
            if e.start_ns >= lo and e.end_ns <= hi and pred(e.name)]


def module_events(trace: Trace, window: Interval, prefix: str) -> List[Event]:
    """Executions of the program ``jit_<prefix>`` wholly inside the window."""
    lo, hi = window
    want = f"jit_{prefix}("
    return [e for evs in trace.device_modules.values() for e in evs
            if e.name.startswith(want) and e.start_ns >= lo and e.end_ns <= hi]


def exposed_collective_seconds(trace: Trace, window: Interval) -> Dict[str, float]:
    """Per device: seconds inside the window in which a collective was
    running (synchronous on ``XLA Ops``, or in flight on ``Async XLA Ops``)
    and no other operation was — communication that compute did not hide."""
    out: Dict[str, float] = {}
    for dev, evs in trace.device_ops.items():
        coll = [e for e in evs if is_collective(e.name)]
        coll += [e for e in trace.device_async.get(dev, ()) if is_collective(e.name)]
        compute = [e for e in evs
                   if not is_collective(e.name) and not is_container(e.name)]
        c = union(clip(spans_of(coll), window))
        k = union(clip(spans_of(compute), window))
        out[dev] = total(subtract(c, k)) / 1e9
    return out


def idle_gaps(trace: Trace, window: Interval, top: int = 10,
              ) -> List[Tuple[str, float]]:
    """The device's idle time inside the window (first device), attributed
    to what the host was doing: each idle interval goes to the innermost
    (shortest) host span that covers its midpoint, ``(no span)`` where none
    does. Returns span names by total idle seconds."""
    if not trace.device_ops:
        return []
    dev = sorted(trace.device_ops)[0]
    busy = union(clip(spans_of(trace.device_ops[dev]), window))
    gaps = subtract([window], busy)
    acc: Dict[str, float] = {}
    spans = sorted(trace.host_spans, key=lambda e: e.dur_ns)
    for s, e in gaps:
        mid = (s + e) / 2
        owner = next((h.name for h in spans if h.start_ns <= mid <= h.end_ns),
                     "(no span)")
        acc[owner] = acc.get(owner, 0.0) + (e - s) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]
