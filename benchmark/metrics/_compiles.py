"""The program's own account of its compiles: the ``xla.compile`` spans of
``runtime.tracing.TRACER`` (one per trace, lowering and backend compile or
cache load that ``tpu.profiling.watch_compiles`` saw, with JAX's own
``time.time()`` stamps) and the counters ``xla_compiles_total`` and
``xla_compile_seconds_total`` of ``runtime.metrics.METRICS``, read after the
run as ``_requests.py`` reads the request spans. ``obs["compile_spans"]``,
``obs["compiles_counted"]`` and ``obs["window_open_ns"]``, where a test
provides them, are taken first.

The ring holds 4,096 spans of every name. A reader gets no span at all,
never a partial sum, where the ring holds fewer backend spans than the
first counter counted or fewer seconds than the second: a span was lost.
A program without the watcher (the parent commit's) has neither spans nor
counters and every reader returns None.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

SPAN = "xla.compile"
PREWARM = "serving.engine.prewarm"
# a span's stamps are JAX's float seconds turned into whole nanoseconds
ROUNDING_S = 2e-6


def seconds(span) -> float:
    return (span.end_ns - span.start_ns) / 1e9


def is_backend(span, outcome: Optional[str] = None) -> bool:
    a = span.attributes
    return a.get("phase") == "backend" and outcome in (None, a.get("outcome"))


def counted_seconds(span) -> float:
    """What ``xla_compile_seconds_total`` added for this span: a hit counts
    the seconds its read took, everything else its own."""
    if is_backend(span, "loaded"):
        return float(span.attributes.get("retrieval_s", 0.0))
    return seconds(span)


def events(obs: Dict[str, Any]) -> Optional[List[Any]]:
    """Every ``xla.compile`` span of the process, by start; None where the
    program emits none or the ring has lost one."""
    spans, counted = obs.get("compile_spans"), obs.get("compiles_counted")
    if spans is None:
        from kubeflow_tpu.runtime.tracing import TRACER

        spans = TRACER.finished_spans(SPAN)
    if counted is None:
        from kubeflow_tpu.runtime.metrics import METRICS

        counted = {"compiles": METRICS.total("xla_compiles_total"),
                   "seconds": METRICS.total("xla_compile_seconds_total")}
    if not spans:
        return None
    if sum(1 for s in spans if is_backend(s)) < counted["compiles"]:
        return None
    if sum(map(counted_seconds, spans)) < counted["seconds"] - ROUNDING_S * len(spans):
        return None
    return sorted(spans, key=lambda s: s.start_ns)


def total(spans: Optional[List[Any]], of: Callable[[Any], float] = lambda span: 1) -> Optional[float]:
    """Σ ``of(span)`` (by default: how many), or None where there is no
    account to sum."""
    return None if spans is None else sum(map(of, spans))


def opening_ns(obs: Dict[str, Any]) -> float:
    """The window's opening on the wall clock, the spans' clock. The harness
    counts ``setup_s`` on ``perf_counter`` from its import; both clocks are
    read here, a few ms after each other at most."""
    if "window_open_ns" in obs:
        return float(obs["window_open_ns"])
    from benchmark import harness

    since = time.perf_counter() - (harness._T0 + obs["setup_s"])
    return (time.time() - since) * 1e9


def before_opening(obs: Dict[str, Any], keep: Callable[[Any], bool] = lambda span: True,
                   ) -> Optional[List[Any]]:
    """The spans (of those ``keep`` takes) that START before the window
    opens: set-up's."""
    spans = events(obs)
    if spans is None:
        return None
    opened = opening_ns(obs)
    return [s for s in spans if s.start_ns < opened and keep(s)]


def in_window(obs: Dict[str, Any], keep: Callable[[Any], bool] = lambda span: True,
              ) -> Optional[List[Any]]:
    """The spans (of those ``keep`` takes) that START inside the measured
    window (serve cells: a compile or a load there sits in somebody's
    latency)."""
    if obs["kind"] != "serve":
        return None
    spans = events(obs)
    if spans is None:
        return None
    opened = opening_ns(obs)
    return [s for s in spans
            if opened <= s.start_ns <= opened + obs["window_s"] * 1e9 and keep(s)]


def prewarms(obs: Dict[str, Any]) -> Optional[List[Any]]:
    """The engine's ``serving.engine.prewarm`` spans; None in a cell that
    serves nothing or a program that has none."""
    if obs["kind"] != "serve":
        return None
    spans = obs.get("prewarm_spans")
    if spans is None:
        from kubeflow_tpu.runtime.tracing import TRACER

        spans = TRACER.finished_spans(PREWARM)
    return list(spans) or None
