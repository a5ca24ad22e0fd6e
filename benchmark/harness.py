"""What every runner shares: the cell (found by name in ``BENCHMARK.json``),
the compile cache, the device report, the profiler session, the metric
readers (one file each under ``benchmark/metrics/``) and the result line.

A runner (``benchmark/runners/<runner>.py``) exposes ``run(cell, harness)``
and returns an ``Outcome``. A metric reader exposes ``read(obs)`` and
returns a number, or ``None`` when there is nothing for it to read — the
harness then leaves that metric out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SPAN = "bench.window"


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    mix: Dict[str, Any]
    params: Dict[str, Any]          # benchmark/cells/<name>.json
    seed: int
    seconds: float
    trace: bool
    spec: Dict[str, Any]            # BENCHMARK.json

    @property
    def runner(self) -> str:
        return self.mix["runner"]

    @property
    def deploy(self) -> Dict[str, Any]:
        """The configuration's settings for this runner (slots, arena,
        optimizer...): how this model is deployed in that role."""
        return self.config["runners"][self.runner]

    @property
    def limits(self) -> Dict[str, float]:
        return self.params["limits"]


@dataclass
class Outcome:
    obs: Dict[str, Any]
    attempted: int
    failed: int
    setup_s: float
    checks: List[Tuple[str, float, float]]       # name, value, limit
    memory_peak_bytes: int
    trace_dir: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)


def load_cell(workload: str, seed: int, seconds: float, trace: bool) -> Cell:
    from . import traffic

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    params = json.loads((HERE / "cells" / f"{workload}.json").read_text())
    mix = traffic.load_mix(entry["traffic"], params.get("traffic"))
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], mix, params, seed, seconds, trace, spec)


def setup_compile_cache() -> str:
    """Before JAX is imported. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads
    it itself and nothing is set in code. Unset: ``<checkout>/.jax_cache``,
    the fixed path the program's own ``enable_compile_cache()`` uses too.
    Every program is cached, however short its compile: the engine makes
    hundreds of sub-second ones."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


def require_devices(chips: int) -> List[Any]:
    """The chips the cell asks for, or no run at all."""
    import jax

    from .peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind}); the benchmark runs on the chip only")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def device_report(devices: List[Any], memory_peak_bytes: int) -> Dict[str, Any]:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(memory_peak_bytes)}


def allocator_peak(devices: List[Any]) -> int:
    """``peak_bytes_in_use`` on the fullest chip, as the allocator counts
    it: live buffers, NOT a running program's temporaries (PR 21)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Profiler:
    """One profiler session around part of the window. Python-function
    tracing is off (it slows a many-threaded server and bloats the file);
    ``TraceAnnotation`` spans are kept."""

    def __init__(self, cell: Cell):
        self.dir = str(ROOT / ".bench_trace" / cell.name)
        self._span = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(TRACE_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


_T0 = time.perf_counter()


def note(what: str) -> None:
    """Where set-up's seconds go, on stderr (since this module's import)."""
    print(f"[setup {time.perf_counter() - _T0:7.2f}s] {what}", file=sys.stderr, flush=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_for(cell: Cell, kind: str) -> List[Dict[str, Any]]:
    return [m for m in cell.spec[kind]
            if "workloads" not in m or cell.name in m["workloads"]]


def reduce_trace(outcome: Outcome, devices: List[Any]) -> Dict[str, Any]:
    """Loads the trace into ``obs`` and returns ``busy_s``, ``window_s`` and
    the breakdown."""
    from . import trace_reduce as tr

    trace = tr.load(tr.find_xplane(outcome.trace_dir))
    window = tr.window_of(trace, TRACE_SPAN)
    busy = tr.busy_by_device(trace, window)
    if not busy or max(busy.values()) <= 0:
        raise SystemExit("traced run: no operation ran on the device")
    outcome.obs.update(trace=trace, trace_window=window, busy_by_device=busy)
    return {
        "busy_s": sum(busy.values()) / len(busy),
        "window_s": (window[1] - window[0]) / 1e9,
        "breakdown": {
            "device_ops": [[k, v] for k, v in tr.op_seconds(trace, window)],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps(trace, window)],
        },
    }


def emit(cell: Cell, outcome: Outcome, devices: List[Any]) -> int:
    """Reads the metrics, prints the checks on stderr and the result line
    as the last line of stdout."""
    device = device_report(devices, outcome.memory_peak_bytes)
    breakdown = None
    outcome.obs["setup_s"] = outcome.setup_s
    if cell.trace:
        traced = reduce_trace(outcome, devices)
        breakdown = traced.pop("breakdown")
        device.update(traced)
    values: Dict[str, Any] = {}
    for m in metrics_for(cell, "per_layer" if cell.trace else "end_to_end"):
        value = load_reader(m["name"])(outcome.obs)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = {name: {"value": float(v), "limit": float(lim)}
              for name, v, lim in outcome.checks}
    correct = bool(checks) and all(
        c["value"] == c["value"] and c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": values, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(outcome.extra)
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
