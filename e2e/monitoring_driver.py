"""Monitoring-plane e2e: federation, burn-rate alerting, and scrape-backed
autoscaling over REAL HTTP (ISSUE 10 acceptance criteria, CI job
monitoring-e2e).

Boots THREE distinct processes that each expose /metrics — a ModelServer
hosting a 2-replica tiny-GPT fleet (this process) plus two subprocess
"ops" servers — registers them as annotated Pods in an in-process
apiserver, and drives one MonitoringPlane against the set:

1. **Federation** — the scraper discovers all three targets from Pod
   annotations, ``up == 1`` for each, and ``/federate`` (served over
   HTTP) re-exposes every process's series with instance/job labels in a
   dialect our own parser accepts.
2. **Burn-rate lifecycle** — a slow-replica fault (``step_delay_s``, the
   same knob the chaos monkey's ``slow_replica`` uses) pushes every TTFT
   past the 0.25s threshold; the multi-window burn-rate alert goes
   pending → firing and emits exactly ONE deduplicated Warning Event
   (count > 1); removing the fault and pushing fast traffic resolves it
   (``alerts_firing`` back to 0, a Normal ...Resolved Event).
3. **Scrape-backed autoscaling** — an ``SLOAutoscaler`` reading a
   ``FederatedWindowSource`` (the TSDB, NOT the in-process registry)
   scales the fleet 2 → 3 on the scraped breach.
4. **Dashboard** — ``/api/metrics/platform`` reports the three targets
   and a federated serving p99.

Exit 0 on success, 1 with a JSON failure report otherwise. CPU-only,
tiny config, ~tens of seconds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

OPS_PROCS = 2
TTFT_THRESHOLD_S = 0.25  # a real TTFT_BUCKETS bound
STEP_DELAY_S = 0.45      # slow-replica fault: every TTFT lands past 0.25s
TICK_S = 0.15

_OPS_SCRIPT = """
import sys, time
from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.runtime.obs import mount_observability
from kubeflow_tpu.web.http import App

METRICS.gauge("workqueue_depth", queue="default").set(3)
METRICS.counter("workqueue_adds_total", queue="default").inc(7)
app = App("ops")
mount_observability(app)
srv = app.serve(0)
print(srv.port, flush=True)
time.sleep(600)
"""


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.read()


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


class _Traffic:
    """Background request loops so the tick loop never blocks on a slow
    (fault-injected) completion."""

    def __init__(self, url: str, prompt: list, threads: int = 2) -> None:
        self.url = url
        self.prompt = prompt
        self.sent = 0
        self.errors: list = []
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._run, daemon=True)
                         for _ in range(threads)]

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                _post(self.url, {"instances": [self.prompt]})
                self.sent += 1
            except Exception as e:  # noqa: BLE001 — recorded, asserted below
                self.errors.append(str(e))
                if len(self.errors) > 10:
                    return

    def __enter__(self) -> "_Traffic":
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=120)


class _AutoscalerCadence:
    """Tick the autoscaler on its own slow cadence: evaluation windows must
    be long enough to hold traffic (a scrape-rate window of a ~2s/request
    workload is empty more often than not, and an empty-but-fresh window
    legitimately reads as idle)."""

    def __init__(self, autoscaler, every_s: float = 2.5) -> None:
        self.autoscaler = autoscaler
        self.every_s = every_s
        self._last = 0.0

    def maybe_tick(self) -> None:
        now = time.monotonic()
        if now - self._last >= self.every_s:
            self._last = now
            self.autoscaler.tick()


def _tick_until(plane, predicate, timeout: float, desc: str,
                cadence=None) -> list:
    """Drive ``plane.tick()`` (and optionally the autoscaler cadence) on
    real time until ``predicate(statuses)`` holds."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        statuses = plane.tick()
        if cadence is not None:
            cadence.maybe_tick()
        if predicate(statuses):
            return statuses
        time.sleep(TICK_S)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


def run() -> dict:
    from kubeflow_tpu.api.meta import new_object
    from kubeflow_tpu.apiserver.client import Client
    from kubeflow_tpu.apiserver.store import Store
    from kubeflow_tpu.monitoring import (
        SCRAPE_ANNOTATION,
        SCRAPE_JOB_ANNOTATION,
        SCRAPE_URL_ANNOTATION,
        BurnRateWindow,
        MonitoringPlane,
        SLOBurnRateAlert,
        parse_exposition,
    )
    from kubeflow_tpu.runtime.obs import mount_observability
    from kubeflow_tpu.serving.autoscaler import (
        AutoscalerConfig,
        FederatedWindowSource,
        SLOAutoscaler,
    )
    from kubeflow_tpu.serving.server import ModelServer, gpt_served_model
    from kubeflow_tpu.services.dashboard import make_dashboard_app
    from kubeflow_tpu.web.auth import AuthConfig
    from kubeflow_tpu.web.http import App

    report: dict = {"ok": True}
    procs: list = []
    closers: list = []
    try:
        # -- three distinct processes exposing /metrics ----------------------
        model = gpt_served_model(name="gpt", tiny=True, max_new_tokens=4,
                                 replicas=2)
        model.max_replicas = 3
        server = ModelServer()
        server.add(model)
        fleet = model._continuous_engine()
        httpd = server.serve(0)
        closers += [httpd.close, server.close, model.close]
        base = f"http://127.0.0.1:{httpd.port}"

        urls = [f"{base}/metrics"]
        for i in range(OPS_PROCS):
            proc = subprocess.Popen(
                [sys.executable, "-c", _OPS_SCRIPT],
                # this process hosts the fleet; a chip has one owner
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                stdout=subprocess.PIPE, text=True)
            procs.append(proc)
            port = int(proc.stdout.readline().strip())
            urls.append(f"http://127.0.0.1:{port}/metrics")

        # -- discovery: three annotated Pods in an in-process apiserver ------
        client = Client(Store())
        for i, url in enumerate(urls):
            job = "serving" if i == 0 else "ops"
            client.create(new_object(
                "v1", "Pod", f"target-{i}", "default",
                annotations={SCRAPE_ANNOTATION: "true",
                             SCRAPE_URL_ANNOTATION: url,
                             SCRAPE_JOB_ANNOTATION: job}))

        plane = MonitoringPlane(client=client, stale_after=3, timeout_s=5.0)
        plane.rules.repeat_s = 1.0  # fast repeat: the dedup assertion needs >=2 emissions
        plane.rules.add(SLOBurnRateAlert(
            name="TtftBurn",
            metric="serving_ttft_seconds",
            threshold_s=TTFT_THRESHOLD_S,
            objective=0.9,
            windows=(BurnRateWindow(short_s=1.5, long_s=4.0, factor=2.0,
                                    severity="page"),),
            for_s=0.2,
        ))

        # -- (1) federation of three processes -------------------------------
        up = plane.scraper.scrape_once()
        assert len(up) == 3 and all(up.values()), f"all targets up: {up}"
        monitor_app = App("monitor")
        mount_observability(monitor_app)
        plane.mount(monitor_app)
        monitor_httpd = monitor_app.serve(0)
        closers.append(monitor_httpd.close)
        fed_url = f"http://127.0.0.1:{monitor_httpd.port}/federate"

        prompt = list(range(1, 9))
        predict = f"{base}/v1/models/gpt:predict"
        for _ in range(4):  # warm-up: fast traffic seeds both SLO histograms
            _post(predict, {"instances": [prompt]})
        plane.tick()
        families = parse_exposition(_get(fed_url).decode())
        by_name = {f.name: f for f in families}
        assert "workqueue_depth" in by_name, "ops subprocess series federated"
        ops_instances = {s.labels["instance"]
                         for s in by_name["workqueue_depth"].samples}
        assert len(ops_instances) == OPS_PROCS, ops_instances
        assert "serving_ttft_seconds" in by_name, "serving histogram federated"
        bucket = by_name["serving_ttft_seconds"].samples[0]
        assert bucket.labels["job"] == "serving"
        assert len({s.labels["instance"] for f in families
                    for s in f.samples if "instance" in s.labels}) == 3, \
            "three distinct processes must federate"
        report["federated_targets"] = sorted(
            lab["instance"] for lab, _t, v in plane.tsdb.latest("up"))
        report["federated_families"] = len(families)

        # -- (2)+(3) burn-rate firing + scrape-backed scale-up ---------------
        autoscaler = SLOAutoscaler(fleet, AutoscalerConfig(
            ttft_slo=TTFT_THRESHOLD_S, queue_wait_slo=10.0, quantile=0.9,
            breach_ticks=2, idle_ticks=10_000, cooldown_ticks=0),
            source=FederatedWindowSource(plane.tsdb))
        cadence = _AutoscalerCadence(autoscaler)
        statuses = plane.tick()
        assert statuses[0]["state"] == "inactive", statuses
        for handle in fleet.live_handles():  # the chaos monkey's slow_replica knob
            handle.engine.step_delay_s = STEP_DELAY_S
        with _Traffic(predict, prompt) as slow_traffic:
            statuses = _tick_until(
                plane, lambda ss: ss[0]["state"] == "firing", 45.0,
                "burn-rate alert to fire", cadence=cadence)
            report["burn_short_at_fire"] = statuses[0]["burn_short"]
            # keep ticking while firing: emissions must AGGREGATE
            _tick_until(plane,
                        lambda ss: _events(client, "TtftBurn")
                        and _events(client, "TtftBurn")[0]["count"] >= 2,
                        20.0, "deduplicated Event count to climb",
                        cadence=cadence)
            _tick_until(plane, lambda ss: fleet.desired_replicas == 3, 60.0,
                        "scrape-backed scale-up 2 -> 3", cadence=cadence)
        assert slow_traffic.errors == [], slow_traffic.errors
        firing_events = _events(client, "TtftBurn")
        assert len(firing_events) == 1, \
            f"firing must dedup to ONE Event, got {len(firing_events)}"
        assert firing_events[0]["count"] >= 2
        assert firing_events[0]["type"] == "Warning"
        assert autoscaler.last["source"] == "federated"
        fleet_doc = json.loads(_get(f"{base}/debug/fleet"))
        assert fleet_doc["desired_replicas"] == 3, fleet_doc
        report["event_count"] = firing_events[0]["count"]
        report["autoscaled_to"] = fleet_doc["desired_replicas"]
        report["autoscaler_source"] = autoscaler.last["source"]
        report["slow_requests"] = slow_traffic.sent

        # -- (2b) recovery resolves the alert --------------------------------
        for handle in fleet.live_handles():
            handle.engine.step_delay_s = 0.0
        with _Traffic(predict, prompt) as fast_traffic:
            statuses = _tick_until(
                plane, lambda ss: ss[0]["state"] == "resolved", 45.0,
                "burn-rate alert to resolve")
        assert fast_traffic.errors == [], fast_traffic.errors
        from kubeflow_tpu.runtime.metrics import METRICS
        assert METRICS.value("alerts_firing", alertname="TtftBurn",
                             severity="page") == 0.0
        resolved = _events(client, "TtftBurnResolved")
        assert len(resolved) == 1 and resolved[0]["type"] == "Normal"
        report["resolved"] = True
        report["fast_requests"] = fast_traffic.sent

        # -- (4) dashboard speaks federated data -----------------------------
        dash = make_dashboard_app(client, auth=AuthConfig(disable_auth=True),
                                  monitoring=plane)
        overview = dash.call("GET", "/api/metrics/platform?window=30",
                             None, {"kubeflow-userid": "ops@example.com"})
        assert overview.status == 200, overview.body
        doc = overview.body
        assert len(doc["targets"]) == 3, doc["targets"]
        assert all(t["up"] == 1.0 for t in doc["targets"]), doc["targets"]
        assert doc["serving"]["ttftP99"] is not None, \
            "platform p99 must come from federated data"
        report["platform_ttft_p99"] = doc["serving"]["ttftP99"]
        return report
    finally:
        for proc in procs:
            proc.terminate()
        for close in closers:
            try:
                close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        for proc in procs:
            proc.wait(timeout=30)


def _events(client, reason: str) -> list:
    return [e for e in client.list("v1", "Event", "kubeflow-system")
            if e.get("reason") == reason]


def main() -> int:
    try:
        report = run()
    except AssertionError as e:
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"ok": False, "error": str(e)}))
        return 1
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
