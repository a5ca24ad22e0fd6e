"""Attribution-plane e2e: a live train loop, profiled over REAL HTTP
(ISSUE 8 acceptance criteria, CI job attribution-e2e).

Runs a tiny jitted train step under ``StepClock`` with the full phase set
(data_wait / compute / fetch), registers the clock at ``/debug/profile``,
mounts observability on a real server, then asserts:

1. ``GET /debug/profile`` returns JSON that ``json.loads`` cleanly and is
   Chrome-trace-loadable: a ``traceEvents`` list with >= 1 complete
   ("ph": "X") event per step phase per captured step plus one per step,
2. capture-on-demand: ``?steps=N&timeout=S`` issued BEFORE the steps run
   blocks until N fresh steps exist and returns exactly their events,
3. ``/metrics`` carries a nonzero ``training_step_peak_hbm_bytes`` gauge
   (the compiled step's memory_analysis footprint),
4. the attribution report's fraction decomposition sums to 1 and its
   measured phases reconstruct the StepClock step within 5%.

Exit 0 on success, 1 with a JSON failure report. CPU, ~seconds.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

STEPS = 4
PHASES = ("data_wait", "compute", "fetch")


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.read()


def run() -> dict:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.runtime.metrics import METRICS
    from kubeflow_tpu.runtime.obs import mount_observability
    from kubeflow_tpu.runtime.tracing import TRACER
    from kubeflow_tpu.tpu.profiling import StepClock, register_profile_clock
    from kubeflow_tpu.training.attribution import (
        attribution_report, price_callable, record_step_peak_hbm)
    from kubeflow_tpu.training.flops import memory_stats
    from kubeflow_tpu.web.http import App

    @jax.jit
    def train_step(w, x):
        return w - 0.01 * jnp.tanh(x @ w).T @ x / x.shape[0]

    rng = jax.random.PRNGKey(0)
    w = jax.random.normal(rng, (64, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))

    clock = register_profile_clock(
        StepClock(metrics=METRICS.namespace("training"), tracer=TRACER))
    compiled = train_step.lower(w, x).compile()
    record_step_peak_hbm(memory_stats(compiled))

    def step(w):
        with clock.data_wait():
            time.sleep(0.001)  # stands in for the input pipeline
        with clock.compute():
            w = compiled(w, x)
            jax.block_until_ready(w)
        with clock.fetch():
            float(jnp.sum(w))
        clock.end_step()
        return w

    app = App("attribution-e2e")
    mount_observability(app)
    httpd = app.serve(0)
    base = f"http://127.0.0.1:{httpd.port}"
    try:
        for _ in range(STEPS):
            w = step(w)

        # -- 1: snapshot profile is valid Chrome trace -----------------------
        doc = json.loads(_get(f"{base}/debug/profile?steps={STEPS}"))
        events = doc["traceEvents"]
        assert doc.get("displayTimeUnit") == "ms", doc.keys()
        complete = [e for e in events if e.get("ph") == "X"]
        for e in complete:
            assert {"name", "ts", "dur", "pid", "tid"} <= set(e), e
        step_events = [e for e in complete if e.get("cat") == "step"]
        assert len(step_events) == STEPS, (len(step_events), STEPS)
        for phase in PHASES:
            n = sum(1 for e in complete
                    if e.get("cat") == "phase" and e["name"] == phase)
            assert n >= STEPS, f"phase {phase}: {n} events < {STEPS} steps"

        # -- 2: capture-on-demand waits for FRESH steps ----------------------
        fresh = 2
        captured = {}

        def capture():
            captured["doc"] = json.loads(
                _get(f"{base}/debug/profile?steps={fresh}&timeout=30"))

        t = threading.Thread(target=capture)
        t.start()
        time.sleep(0.2)  # request must be in its polling wait before we step
        for _ in range(fresh):
            w = step(w)
        t.join(timeout=60)
        assert not t.is_alive(), "on-demand capture never returned"
        got = [e for e in captured["doc"]["traceEvents"]
               if e.get("ph") == "X" and e.get("cat") == "step"]
        assert len(got) == fresh, (len(got), fresh)

        # -- 3: HBM gauge in the exposition ----------------------------------
        text = _get(f"{base}/metrics").decode()
        peak = next((float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                     if ln.startswith("training_step_peak_hbm_bytes")), 0.0)
        assert peak > 0, "training_step_peak_hbm_bytes missing or zero"

        # -- 4: attribution fractions reconstruct the measured step ----------
        cost = price_callable(train_step, w, x, name="train_step",
                              kind="step")
        report = attribution_report([cost], clock=clock, generation="v5e")
        frac_sum = sum(report.fractions.values())
        assert abs(frac_sum - 1.0) < 1e-6, report.fractions
        reconstructed = sum(report.measured.values())
        assert abs(reconstructed - report.step_seconds) \
            <= 0.05 * report.step_seconds, (reconstructed, report.step_seconds)
        return {
            "ok": True,
            "steps": STEPS + fresh,
            "trace_events": len(events),
            "peak_hbm_bytes": peak,
            "fractions": {k: round(v, 4) for k, v in report.fractions.items()},
            "step_seconds": round(report.step_seconds, 6),
        }
    finally:
        httpd.close()


def main() -> int:
    try:
        report = run()
    except AssertionError as e:
        print(json.dumps({"ok": False, "error": str(e)}))
        return 1
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
