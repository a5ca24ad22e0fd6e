"""JAX profiler integration — the TPU answer to the reference's CUPTI
plumbing (jupyter-tensorflow/cuda.Dockerfile:61-71 LD_LIBRARY_PATH surgery;
on TPU the profiler ships with JAX and needs wiring, not drivers).

Used by the notebook/serving images (images/jupyter-jax-tpu exposes :9999)
and by bench/perf work: start a profile server for TensorBoard's profile
plugin to connect to, or capture a step trace programmatically and read
back where the time went.
"""

from __future__ import annotations

import collections
import glob
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Deque, Dict, List, Optional

PROFILE_PORT = 9999

_server_lock = threading.Lock()
_server_started_port: Optional[int] = None


def start_profile_server(port: int = PROFILE_PORT) -> int:
    """Start the in-process profiler gRPC server (idempotent). TensorBoard's
    profile plugin captures from it: tensorboard --logdir=... then
    'capture profile' at <pod-dns>:<port> — reachable through the headless
    service the notebook controller creates."""
    global _server_started_port
    import jax

    with _server_lock:
        if _server_started_port is not None:
            if _server_started_port != port:
                raise RuntimeError(
                    f"profiler server already on port {_server_started_port}; "
                    f"cannot also serve {port} (one server per process)"
                )
            return _server_started_port
        jax.profiler.start_server(port)
        _server_started_port = port
        return port


@contextmanager
def step_trace(logdir: str, name: str = "step"):
    """Capture a programmatic trace into ``logdir`` (xplane protos readable
    by TensorBoard/XProf). Use around a handful of steps, not whole runs."""
    import jax

    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation(name):
            yield


def annotate(name: str, **stats: Any):
    """Named region inside a trace (shows as a range in the timeline, on the
    calling thread's line of the host plane, on the profiler's clock).
    Keyword arguments ride as the event's stats; counts known only once the
    region's work is done are added before it closes with
    ``.set_metadata(**stats)`` on the entered object. With no profiler
    session open the region records nothing and costs about a microsecond,
    so the engine loop keeps its ``serving.engine.*`` regions on always."""
    import jax

    return jax.profiler.TraceAnnotation(name, **stats)


# -- compiles and cache loads: the host's time inside JAX's runtime -----------
#
# JAX reports each phase of a compile through ``jax.monitoring``: a scalar
# when tracing, lowering or the backend's compile begins, and at its end a
# time span with ``time.time()`` stamps and the function's name; inside the
# backend's span, on the compiling thread, the persistent cache's events (a
# hit, the seconds the read took, the compile seconds it saved). None fires
# on a call of a program that is compiled already.

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}

_watch_lock = threading.Lock()
_watching = False
_compiles_seen = 0
#: per thread: ``depth``, the phases open on it, and ``hit``, the cache
#: events of the backend phase among them
_compiling = threading.local()


def _on_phase_begin(event: str, _value: float, **_: Any) -> None:
    if event in _COMPILE_PHASES:
        _compiling.depth = getattr(_compiling, "depth", 0) + 1


def _on_cache_hit(event: str, **_: Any) -> None:
    if event == _CACHE_HIT:
        _compiling.hit = {}


def _on_cache_seconds(event: str, seconds: float, **_: Any) -> None:
    key = _CACHE_SECONDS.get(event)
    hit = getattr(_compiling, "hit", None)
    if key is not None and hit is not None:
        hit[key] = float(seconds)


def _on_phase_end(event: str, start: float, end: float, **kw: Any) -> None:
    global _compiles_seen
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    _compiling.depth = outer = max(0, getattr(_compiling, "depth", 1) - 1)
    if outer and phase != "backend":
        # tracing one program traces every jitted function it calls (a toy
        # engine: 2,600 of 2,900 events): their time is their caller's
        return
    from kubeflow_tpu.runtime.metrics import METRICS
    from kubeflow_tpu.runtime.tracing import TRACER

    attrs: Dict[str, Any] = {"phase": phase, "fun_name": str(kw.get("fun_name", ""))}
    counted, seconds = phase, end - start
    if phase == "backend":
        hit = getattr(_compiling, "hit", None)
        _compiling.hit = None
        attrs["outcome"] = "compiled" if hit is None else "loaded"
        if hit is not None:
            attrs.update(hit)
            counted, seconds = "cache_load", hit.get("retrieval_s", 0.0)
        METRICS.counter("xla_compiles_total", outcome=attrs["outcome"]).inc()
        with _watch_lock:
            _compiles_seen += 1
    METRICS.counter("xla_compile_seconds_total", phase=counted).inc(seconds)
    TRACER.emit_span("xla.compile", int(start * 1e9), int(end * 1e9), **attrs)


def watch_compiles() -> None:
    """Name the host's time inside JAX's runtime, from now on and for the
    life of the process (idempotent; called where the modules that hold the
    program's entry points are imported, so that the programs compiled
    before an engine or a train step is built are counted too).

    Every trace, lowering and backend compile that is not part of another
    on its thread becomes one ``xla.compile`` span of
    ``runtime.tracing.TRACER`` with JAX's own stamps (``phase`` = ``trace``
    | ``lower`` | ``backend``, ``fun_name``); a ``backend`` span says
    whether the executable was ``compiled`` or ``loaded`` from the
    persistent cache (``outcome``; a load carries ``retrieval_s`` and
    ``saved_s``). The counters ``xla_compiles_total{outcome}`` and
    ``xla_compile_seconds_total{phase}`` (``backend``: misses only;
    ``cache_load``: the reads of the hits) say the same with no trace open,
    and ``compiles_seen()`` counts the backend spans. A call of a compiled
    program fires none of JAX's events and costs nothing here."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_phase_begin)
        monitoring.register_event_listener(_on_cache_hit)
        monitoring.register_event_duration_secs_listener(_on_cache_seconds)
        monitoring.register_event_time_span_listener(_on_phase_end)
        _watching = True


def compiles_seen() -> int:
    """Executables this process has compiled or loaded since
    ``watch_compiles()``: a caller that reads it before and after a call
    knows whether the call compiled."""
    return _compiles_seen


class StepClock:
    """Wall-clock step breakdown for training/bench loops.

    The profiler trace (above) answers "where did the time go" offline; the
    clock answers it live, per step, with host-side timers cheap enough to
    leave on: wrap each phase of the loop body and ``end_step()`` at the
    bottom. The canonical phases:

        with clock.compile(): compiled = step_fn.lower(...).compile()
        for batch in data:                # via device_prefetch(clock=clock)
            with clock.compute(): out = compiled(state, batch)
            with clock.fetch():   loss = float(out["loss"])   # D2H sync
            clock.end_step()

    Each record holds the measured phases plus ``total`` (wall since the
    previous ``end_step``) and ``other`` (total minus measured — dispatch
    overhead, Python, logging). Compile time accumulates separately and is
    never charged to a step, so the first-step XLA compile can't masquerade
    as slow data loading (the classic misread this exists to kill). With a
    ``metrics`` namespace (``METRICS.namespace("train")``) every phase also
    lands in ``<ns>_step_<phase>_seconds`` histograms for ``/metrics``.
    With a ``tracer`` (``runtime.tracing.TRACER``) every ``end_step()``
    additionally emits one ``span_name`` span covering the step, its phases
    attached as events — so a bench/dryrun's training timeline shows up in
    ``/debug/traces`` next to the serving requests.

    Phase events are always retained per step in a bounded ring
    (``keep_steps``, default 512) so the timeline survives without a
    tracer: ``to_chrome_trace()`` renders the recorded steps as a
    Chrome-trace-event document (the ``trace.json`` Perfetto and
    chrome://tracing load), and ``register_profile_clock()`` publishes it
    at ``GET /debug/profile`` on every observability-mounted server.
    """

    def __init__(self, metrics: Optional[Any] = None,
                 tracer: Optional[Any] = None,
                 span_name: str = "train.step",
                 keep_steps: int = 512) -> None:
        self._metrics = metrics
        self._tracer = tracer
        self._span_name = span_name
        self.compile_s = 0.0
        self.steps: List[Dict[str, float]] = []
        self.notes: Dict[str, float] = {}
        self._current: Dict[str, float] = {}
        self._anchor = time.perf_counter()
        self._step_start_ns = time.time_ns()
        self._events: List[Dict[str, Any]] = []
        #: per-step phase-event history for to_chrome_trace(): bounded so a
        #: long training run can't grow host memory without limit
        self._step_records: Deque[Dict[str, Any]] = collections.deque(
            maxlen=keep_steps)

    def note(self, key: str, value: float) -> None:
        """Attach a derived scalar (analytic comm bytes, bubble fraction —
        things computed about the step rather than timed in it) so it rides
        along in ``summary()``/metrics next to the measured phases."""
        self.notes[key] = float(value)
        if self._metrics is not None:
            self._metrics.gauge(key).set(float(value))

    @contextmanager
    def phase(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - start
            self._current[name] = self._current.get(name, 0.0) + dt
            if self._metrics is not None:
                self._metrics.histogram(f"step_{name}_seconds").observe(dt)
            # always recorded (span-event shape; start derives from end −
            # seconds): the chrome-trace timeline must not require a tracer
            self._events.append({"name": name,
                                 "timeUnixNano": time.time_ns(),
                                 "attributes": {"seconds": dt}})

    # The canonical phases as methods so call sites stay greppable.
    def data_wait(self):
        """Host blocked waiting on the input pipeline (H2D not yet hidden)."""
        return self.phase("data_wait")

    def compute(self):
        """Dispatch + device execution (through ``block_until_ready``)."""
        return self.phase("compute")

    def fetch(self):
        """D2H readback of step outputs (loss/metrics scalars)."""
        return self.phase("fetch")

    def collective(self):
        """Host blocked on cross-worker synchronization (barriers, collective
        dispatch waits) — the straggler plane's skew signal: one slow worker
        inflates every peer's collective_wait, not their compute."""
        return self.phase("collective_wait")

    @contextmanager
    def compile(self):
        """XLA compile — accumulated separately, never charged to a step."""
        start = time.perf_counter()
        try:
            yield
        finally:
            self.compile_s += time.perf_counter() - start
            if self._metrics is not None:
                self._metrics.gauge("compile_seconds").set(self.compile_s)
            # Reset the anchors ONLY. Clearing self._events here silently
            # dropped phase events recorded earlier in the same step (a
            # data_wait timed before a mid-loop recompile vanished from the
            # step span); already-recorded events must survive.
            self._anchor = time.perf_counter()
            if not self._events:
                self._step_start_ns = time.time_ns()

    def mark(self) -> None:
        """Reset the wall anchor without recording — call after untimed
        work between steps (warmup executions, logging) so the next step's
        ``total``/``other`` doesn't absorb it. Phase events already recorded
        in the open step are preserved (see ``compile()``)."""
        self._anchor = time.perf_counter()
        if not self._events:
            self._step_start_ns = time.time_ns()

    def end_step(self) -> Dict[str, float]:
        now = time.perf_counter()
        now_ns = time.time_ns()
        rec = dict(self._current)
        rec["total"] = now - self._anchor
        rec["other"] = max(0.0, rec["total"] - sum(self._current.values()))
        self.steps.append(rec)
        if self._metrics is not None:
            for k, v in rec.items():
                self._metrics.gauge("step_phase_seconds", phase=k).set(v)
        self._step_records.append({
            "step": len(self.steps),
            "start_ns": self._step_start_ns,
            "end_ns": now_ns,
            "phases": list(self._events),
            "rec": rec,
        })
        if self._tracer is not None:
            self._tracer.emit_span(
                self._span_name, self._step_start_ns, now_ns,
                events=self._events,
                **{"step": len(self.steps),
                   **{f"phase.{k}": round(v, 6) for k, v in rec.items()}})
        self._step_start_ns = now_ns
        self._events = []
        self._current = {}
        self._anchor = now
        return rec

    def to_chrome_trace(self, steps: Optional[int] = None,
                        tid: int = 1) -> Dict[str, Any]:
        """The last ``steps`` recorded steps (all retained when None) as a
        Chrome-trace-event document: one complete ("ph": "X") event per
        step named ``span_name`` with its phase means in ``args``, plus one
        complete event per measured phase (start derived from the phase
        event's end − duration). ``json.dumps`` of the return value is a
        ``trace.json`` Perfetto and chrome://tracing open directly."""
        records = list(self._step_records)
        if steps is not None:
            records = records[-max(0, steps):]
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        for r in records:
            events.append({
                "name": self._span_name,
                "cat": "step",
                "ph": "X",
                "ts": r["start_ns"] / 1e3,
                "dur": max(0.0, (r["end_ns"] - r["start_ns"]) / 1e3),
                "pid": pid,
                "tid": tid,
                "args": {"step": r["step"],
                         **{k: round(v, 6) for k, v in r["rec"].items()}},
            })
            for ev in r["phases"]:
                dur_us = float(ev["attributes"].get("seconds", 0.0)) * 1e6
                events.append({
                    "name": ev["name"],
                    "cat": "phase",
                    "ph": "X",
                    "ts": ev["timeUnixNano"] / 1e3 - dur_us,
                    "dur": dur_us,
                    "pid": pid,
                    "tid": tid,
                    "args": {"step": r["step"]},
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def summary(self) -> Dict[str, float]:
        """Per-phase mean seconds across recorded steps, plus ``compile_s``
        and the step count — the dict bench.py emits as ``step_breakdown``."""
        out: Dict[str, float] = {}
        if self.steps:
            keys = sorted(set().union(*self.steps))
            n = len(self.steps)
            for k in keys:
                out[k] = sum(s.get(k, 0.0) for s in self.steps) / n
        out.update(self.notes)
        out["compile_s"] = self.compile_s
        out["steps"] = float(len(self.steps))
        return out


def profile_step(
    fn: Callable[..., Any], *args: Any, logdir: str, iters: int = 3, **kwargs: Any
) -> Dict[str, Any]:
    """Run ``fn`` under the profiler (after one untraced warmup for compile)
    and return {result, trace_files}. The capture covers ``iters`` steps so
    steady-state behavior dominates over first-step noise."""
    import jax

    result = fn(*args, **kwargs)  # warmup/compile outside the trace
    jax.block_until_ready(result)
    with step_trace(logdir):
        for _ in range(iters):
            result = fn(*args, **kwargs)
        jax.block_until_ready(result)
    traces = sorted(
        glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    )
    return {"result": result, "trace_files": traces}


# -- /debug/profile: on-demand step capture over HTTP -------------------------
#
# A training/bench loop registers its StepClock once; every server that
# mounts observability (ops server, apiserver, ModelServer) then serves the
# loop's live timeline as Perfetto-loadable Chrome-trace JSON — the
# "download trace.json from the running job" workflow without a TensorBoard
# deployment in the loop.

#: registered clocks by name; last registration per name wins (what
#: per-incarnation ElasticTrainer restarts and per-test clocks need)
_PROFILE_CLOCKS: Dict[str, "StepClock"] = {}


def register_profile_clock(clock: "StepClock", name: str = "train") -> "StepClock":
    """Publish ``clock`` at ``GET /debug/profile`` (query: ``?steps=N`` last
    N steps, ``?clock=<name>`` one clock, ``?timeout=S`` wait up to S
    seconds for N *fresh* steps — the on-demand capture). Returns the clock
    so call sites can register at construction."""
    from kubeflow_tpu.runtime import obs  # lazy: profiling must not drag HTTP in

    _PROFILE_CLOCKS[name] = clock
    obs.register_debug_source("profile", _profile_debug_source)
    return clock


def _profile_debug_source(req: Any) -> Dict[str, Any]:
    from kubeflow_tpu.web.http import HttpError

    try:
        steps = int(req.query1("steps", "16"))
        timeout = float(req.query1("timeout", "0"))
    except ValueError:
        raise HttpError(400, "steps/timeout must be numeric") from None
    name = req.query1("clock") or None
    if name is not None and name not in _PROFILE_CLOCKS:
        raise HttpError(
            404, f"unknown clock {name!r}; registered: {sorted(_PROFILE_CLOCKS)}")
    selected = {name: _PROFILE_CLOCKS[name]} if name else dict(_PROFILE_CLOCKS)
    if timeout > 0:
        # capture-on-demand: wait for `steps` steps recorded AFTER the
        # request, so the trace answers "what is the loop doing right now"
        deadline = time.monotonic() + timeout
        baselines = {n: len(c.steps) for n, c in selected.items()}
        while time.monotonic() < deadline:
            if all(len(c.steps) >= baselines[n] + steps
                   for n, c in selected.items()):
                break
            time.sleep(0.02)
    events: List[Dict[str, Any]] = []
    for tid, (_n, clock) in enumerate(sorted(selected.items()), start=1):
        events.extend(clock.to_chrome_trace(steps=steps, tid=tid)["traceEvents"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}
