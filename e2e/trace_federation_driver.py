"""Trace federation e2e: one gang-bind journey traced across three REAL
processes (CI job trace-federation-e2e).

The driver process plays the user edge: it sets its Tracer identity to
``loadgen``, mints a W3C traceparent, and submits a gang through
:class:`~kubeflow_tpu.scale.loadgen.LoadGenerator` against an apiserver
running as ``python -m kubeflow_tpu.apiserver`` in its own process, with
``python -m kubeflow_tpu.scheduler.core`` reconciling from a third. Then:

1. asserts the injected trace id appears VERBATIM in every bound pod's
   creation and bind traceparent annotations (the write path crossed two
   process hops and kept the context),
2. serves a tiny GPT in-process and sends one predict carrying the SAME
   traceparent, so the ``serving.request`` retire span joins the gang's
   trace — one trace id from user submit to model response,
3. federates all three span buffers with a :class:`TraceCollector`
   (apiserver + scheduler pulled over HTTP, the driver's own ring
   ingested directly) and asserts the assembled trace spans >= 3 services
   with the full journey's span names present,
4. decomposes the trace with ``critical_path()`` and checks the
   queue/cycle/bind segments reconstruct the scheduler's recorded
   ``gang.bind_latency_s`` within 10%, cross-checking the scheduler's
   /metrics histogram and its trace-id exemplar,
5. drives a 2x-budget burst of boring traces plus known serving 500s into
   a small tail-sampled collector and asserts every error trace and the
   slowest gang bind survive while the span bound holds.

Exit 0 on success, 1 with a JSON failure report otherwise. CPU-only; the
whole run is a handful of seconds on the presubmit topology.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

SEED = 14
NODES = int(os.environ.get("TRACE_NODES", "8"))
TAIL_BUDGET = int(os.environ.get("TRACE_TAIL_BUDGET", "48"))
ERROR_PREDICTS = 3


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _post_json(url: str, body: dict, headers: dict = None,
               timeout: float = 60.0):
    data = json.dumps(body).encode()
    hdrs = {"content-type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=data, headers=hdrs, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        payload = resp.read()
    return json.loads(payload) if payload else None


def _metric_value(text: str, name: str, **labels) -> float:
    """Sum of series for ``name`` whose label set includes ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest[:1] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _poll(fn, timeout: float = 30.0, interval: float = 0.1,
          desc: str = "condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        value = fn()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


def run() -> dict:
    from kubeflow_tpu.apiserver.remote import RemoteStore
    from kubeflow_tpu.monitoring.scrape import Target
    from kubeflow_tpu.monitoring.traces import (
        TraceCollector, critical_path, traces_url)
    from kubeflow_tpu.runtime.obs import otlp_traces
    from kubeflow_tpu.runtime.tracing import (
        BIND_TRACEPARENT_ANNOTATION, TRACEPARENT_ANNOTATION, TRACER)
    from kubeflow_tpu.scale.loadgen import LoadGenerator
    from kubeflow_tpu.scale.topology import synth_gangs, synthesize
    from kubeflow_tpu.serving.server import ModelServer, gpt_served_model

    TRACER.service = "loadgen"  # the driver IS the client process
    api_port, ops_port = _free_port(), _free_port()
    base = f"http://127.0.0.1:{api_port}"
    ops = f"http://127.0.0.1:{ops_port}"
    procs: list = []
    closers: list = []
    try:
        # -- three processes: this driver, a real apiserver, a real scheduler
        # (this process serves a model, so it alone may hold a chip)
        child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.apiserver"],
            env={**child_env, "API_PORT": str(api_port)}))
        RemoteStore(base).wait_ready(timeout=60.0)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.scheduler.core"],
            env={**child_env, "APISERVER_URL": base,
                 "METRICS_PORT": str(ops_port)}))
        def ops_up():
            try:
                return _get(f"{ops}/healthz", timeout=2.0)
            except (urllib.error.URLError, OSError):
                return None

        _poll(ops_up, timeout=60.0, interval=0.25,
              desc="scheduler ops endpoints")

        # -- the traced journey: one minted trace id at the user edge -------
        trace_id = f"{SEED:032x}"
        tp = f"00-{trace_id}-{'00ab' * 4}-01"
        topo = synthesize(NODES, seed=SEED)
        gen = LoadGenerator(base, topo, seed=SEED, traceparent=tp)
        registered = gen.register_nodes()
        assert registered == topo.total_nodes, (registered, topo.total_nodes)
        shape = synth_gangs(topo, 1, seed=SEED, prefix="fed", max_size=4)[0]
        gen.submit_gang(shape)
        gen.wait_gangs_bound([shape.name], timeout_s=90.0)

        # (1) trace id verbatim in both pod annotations, on every member
        members = [p for p in gen._list_pods()
                   if p["metadata"]["name"].startswith(f"{shape.name}-")]
        assert len(members) == shape.size, [p["metadata"]["name"] for p in members]
        for pod in members:
            ann = pod["metadata"].get("annotations") or {}
            assert trace_id in ann.get(TRACEPARENT_ANNOTATION, ""), \
                f"creation annotation lost the trace: {ann}"
            assert trace_id in ann.get(BIND_TRACEPARENT_ANNOTATION, ""), \
                f"bind annotation lost the trace: {ann}"

        # (2) a predict under the SAME traceparent: the serving retire span
        # joins the gang's trace
        model = gpt_served_model(name="gpt", tiny=True, max_new_tokens=4,
                                 replicas=2)
        server = ModelServer()
        server.add(model)
        httpd = server.serve(0)
        closers += [httpd.close, server.close, model.close]
        predict = f"http://127.0.0.1:{httpd.port}/v1/models/gpt:predict"
        out = _post_json(predict, {"instances": [list(range(1, 9))]},
                         headers={"traceparent": tp})
        assert out and out.get("predictions"), out

        # (3) federation: pull apiserver + scheduler buffers over HTTP,
        # ingest the driver's own ring, assemble by trace id
        collector = TraceCollector(targets=[
            Target(job="apiserver", url=traces_url(f"{base}/metrics")),
            Target(job="scheduler", url=f"{ops}/debug/traces?limit=4096"),
        ])
        need = {"gang.submit", "apiserver.create", "gang.lifecycle",
                "schedule.bind", "serving.request"}

        def assembled():
            ok = collector.collect_once()
            assert all(ok.values()), f"trace pulls must succeed: {ok}"
            collector.ingest(otlp_traces(TRACER, limit=4096), job="loadgen")
            t = collector.trace(trace_id)
            if not t or not need <= {s["name"] for s in t["spans"]}:
                return None
            # gang.lifecycle only counts once the root closed with the
            # bind-latency observation attached
            roots = [s for s in t["spans"] if s["name"] == "gang.lifecycle"]
            if not any(isinstance(s.get("attributes", {}).get(
                    "gang.bind_latency_s"), (int, float)) for s in roots):
                return None
            return t

        trace = _poll(assembled, timeout=30.0, interval=0.25,
                      desc=f"federated gang-bind trace {trace_id}")
        assert len(trace["services"]) >= 3, \
            f"a gang bind crosses >=3 processes: {trace['services']}"
        retire = [s for s in trace["spans"] if s["name"] == "serving.request"]
        assert retire and retire[0]["traceId"] == trace_id
        assert any("replica" in (s.get("attributes") or {}) for s in retire), \
            "fleet serving spans must carry their replica identity"

        # (4) critical path reconstructs the bind-latency SLI within 10%
        path = critical_path(trace)
        assert path is not None, "gang trace must decompose"
        assert [s["name"] for s in path["segments"]] == ["queue", "cycle", "bind"], path
        measured = path["measuredBindLatencySeconds"]
        assert measured > 0, path
        # 10% relative, with an absolute floor covering thread-wakeup
        # jitter between spans on a loaded CI box
        tolerance = max(0.1 * measured, 0.05)
        assert path["reconstructionError"] <= tolerance, \
            f"segments {path['totalSeconds']}s vs measured {measured}s " \
            f"(error {path['reconstructionError']}s > {tolerance}s)"
        sched_metrics = _get(f"{ops}/metrics").decode()
        assert _metric_value(sched_metrics,
                             "scheduler_bind_latency_seconds_count") >= 1
        assert trace_id in sched_metrics, \
            "bind-latency histogram must expose the trace-id exemplar"
        binds = collector.slowest_binds(n=5)
        assert any(r["traceId"] == trace_id and r["bound"] for r in binds), binds

        # (5) tail sampling under burst: 2x-budget boring traces + known
        # error traces into a small-budget collector
        errors = 0
        for _ in range(ERROR_PREDICTS):
            try:
                # a zero budget expires on arrival: deterministic 504, and
                # the serving dispatch span goes ERROR
                _post_json(predict, {"instances": [list(range(1, 9))],
                                     "timeout_ms": 0})
            except urllib.error.HTTPError as err:
                assert err.code >= 500, err.code
                errors += 1
        assert errors == ERROR_PREDICTS, "expired predicts must 5xx"

        # size the budget from what must survive: every error trace seen by
        # any of the three processes, plus the gang-bind trace (slowest
        # decile). The burst then doubles it with boring one-span traces.
        api_target = Target(job="apiserver", url=traces_url(f"{base}/metrics"))
        sched_target = Target(job="scheduler",
                              url=f"{ops}/debug/traces?limit=4096")
        tail = TraceCollector(max_spans=TAIL_BUDGET)  # budget set below
        docs = [(tail.fetch(api_target), "apiserver"),
                (tail.fetch(sched_target), "scheduler"),
                (otlp_traces(TRACER, limit=4096), "loadgen")]
        by_trace: dict = {}
        for doc, _job in docs:
            for rs in doc["resourceSpans"]:
                for sc in rs["scopeSpans"]:
                    for s in sc["spans"]:
                        by_trace.setdefault(s["traceId"], {})[s["spanId"]] = s
        error_ids = {tid for tid, spans in by_trace.items()
                     if any((s.get("status") or {}).get("code") == "ERROR"
                            for s in spans.values())}
        assert error_ids, "expired predicts must produce error traces"
        protected = error_ids | {trace_id}
        budget = sum(len(by_trace.get(t, {})) for t in protected) + 16
        tail.max_spans = budget
        burst = 2 * budget
        for _ in range(burst):  # boring single-span traces
            _get(f"{base}/healthz")
        for doc, job in docs:
            tail.ingest(doc, job=job)
        tail.add_target(api_target)
        tail.add_target(sched_target)
        tail.collect_once()  # pulls the burst, then enforces the bound
        kept = set(tail.trace_ids())
        assert error_ids <= kept, \
            f"tail sampling dropped error traces: {error_ids - kept}"
        assert trace_id in kept, "slowest gang bind must survive sampling"
        kept_spans = sum(tail.trace(t)["spanCount"] for t in kept)
        assert kept_spans <= budget, (kept_spans, budget)
        assert len(kept) < len(by_trace) + burst, "sampling must drop traces"

        return {
            "ok": True,
            "traceId": trace_id,
            "services": trace["services"],
            "spanCount": trace["spanCount"],
            "criticalPath": path,
            "tail": {"kept_traces": len(kept), "kept_spans": kept_spans,
                     "error_traces": len(error_ids)},
        }
    finally:
        for close in closers:
            close()
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


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
