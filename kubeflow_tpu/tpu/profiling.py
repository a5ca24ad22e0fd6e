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
