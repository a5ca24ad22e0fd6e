"""Observability plane (ISSUE 4): registry upgrades (custom buckets,
quantiles, exemplars, process collector), the mountable /metrics +
/debug surface, traceparent propagation through serving, and the
continuous-batching engine's SLO telemetry."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

import jax

from kubeflow_tpu.models.gpt import GptConfig, GptLM
from kubeflow_tpu.runtime.metrics import METRICS, MetricsRegistry, install_process_collector
from kubeflow_tpu.runtime.obs import mount_observability, otlp_traces
from kubeflow_tpu.runtime.tracing import TRACER, format_traceparent
from kubeflow_tpu.web.http import App


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


# -- registry upgrades --------------------------------------------------------


class TestRegistry:
    def test_custom_buckets_render(self):
        reg = MetricsRegistry()
        reg.histogram("itl_seconds", buckets=(0.001, 0.01)).observe(0.005)
        text = reg.render()
        assert 'itl_seconds_bucket{le="0.001"} 0' in text
        assert 'itl_seconds_bucket{le="0.01"} 1' in text
        assert 'itl_seconds_bucket{le="+Inf"} 1' in text

    def test_bucket_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="already registered with buckets"):
            reg.histogram("h", buckets=(1.0, 5.0))

    def test_omitted_buckets_reuse_registered_ladder(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=(1.0, 2.0), model="a")
        h2 = reg.histogram("h", model="b")  # new label series, no buckets
        assert h2.buckets == (1.0, 2.0)

    def test_quantile_interpolates(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 0.2, 0.4))
        for v in (0.05, 0.15, 0.15, 0.3):
            h.observe(v)
        # rank 2 of 4 falls in the (0.1, 0.2] bucket
        q50 = reg.quantile("lat", 0.5)
        assert 0.1 <= q50 <= 0.2
        assert reg.quantile("lat", 0.0) == 0.0
        with pytest.raises(ValueError):
            reg.quantile("lat", 1.5)

    def test_quantile_aggregates_label_series_and_clamps_inf(self):
        reg = MetricsRegistry()
        reg.histogram("lat", buckets=(0.1,), model="a").observe(0.05)
        reg.histogram("lat", buckets=(0.1,), model="b").observe(99.0)  # +Inf bucket
        assert reg.quantile("lat", 0.99) == 0.1  # clamped to largest finite bound

    def test_quantile_no_data_is_none_not_zero(self):
        """The boundary the SLO rules depend on: a missing or never-observed
        histogram quantiles to None — 0.0 would read as 'perfect latency'."""
        from kubeflow_tpu.runtime.metrics import quantile_from_counts

        reg = MetricsRegistry()
        assert reg.quantile("missing", 0.5) is None
        reg.histogram("empty", buckets=(0.1, 0.5))  # registered, never observed
        assert reg.quantile("empty", 0.99) is None
        assert quantile_from_counts((0.1, 0.5), [0, 0, 0], 0, 0.99) is None
        ns = reg.namespace("sub")
        assert ns.quantile("missing_too", 0.5) is None

    def test_exemplar_from_current_span(self):
        reg = MetricsRegistry()
        with TRACER.span("scoped") as s:
            reg.histogram("h", buckets=(1.0,)).observe(0.5)
        assert f'# {{trace_id="{s.trace_id}"}} 0.5' in reg.render()

    def test_explicit_trace_id_and_count_amortization(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(0.25, count=4, trace_id="ff" * 16)
        assert h.total == 4 and h.sum == pytest.approx(1.0)
        assert 'trace_id="' + "ff" * 16 + '"' in reg.render()

    def test_process_collector_refreshes_on_render(self):
        reg = MetricsRegistry()
        install_process_collector(reg)
        text = reg.render()
        for name in ("process_uptime_seconds", "process_threads",
                     "process_cpu_seconds_total", "process_resident_memory_bytes",
                     "process_gc_collections_total"):
            assert name in text, name
        reg.reset()  # the autouse fixture does this between tests
        assert "process_threads" in reg.render(), "collector must survive reset()"


# -- exposition validity ------------------------------------------------------

TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")
SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
    r"( # \{trace_id=\"[0-9a-f]{32}\"\} -?[0-9.eE+-]+ [0-9.]+)?$"
)


def assert_valid_exposition(text: str) -> None:
    """Line-by-line exposition check: every line is a TYPE line or a sample,
    histogram buckets are cumulative-monotone, _count equals +Inf, and the
    document ends with the OpenMetrics ``# EOF`` terminator."""
    assert text.endswith("\n")
    lines = text.splitlines()
    assert lines and lines[-1] == "# EOF", "missing # EOF terminator"
    buckets = {}  # series key -> [(le, count)]
    counts = {}
    for line in lines[:-1]:
        if not line:
            continue
        if line.startswith("#"):
            assert line != "# EOF", "# EOF before end of document"
            assert TYPE_RE.match(line), f"bad TYPE line: {line!r}"
            continue
        m = SAMPLE_RE.match(line)
        assert m, f"bad sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", float(m.group(3))
        if name.endswith("_bucket"):
            le = re.search(r'le="([^"]*)"', labels).group(1)
            rest = re.sub(r',?le="[^"]*"', "", labels)
            rest = "" if rest == "{}" else rest  # unlabeled series
            buckets.setdefault((name, rest), []).append((le, value))
        elif name.endswith("_count"):
            counts[(name[:-len("_count")] + "_bucket", labels)] = value
    assert buckets, "no histograms in exposition"
    for key, series in buckets.items():
        values = [v for _, v in series]
        assert values == sorted(values), f"non-monotone buckets for {key}"
        assert series[-1][0] == "+Inf"
        if key in counts:
            assert counts[key] == series[-1][1], f"count != +Inf for {key}"


class TestExpositionSurface:
    def test_ops_server_scrape_over_http(self):
        """The control-plane ops server's /metrics parses end to end."""
        from kubeflow_tpu.runtime.bootstrap import serve_ops_endpoints

        METRICS.histogram("controller_reconcile_seconds",
                          controller="X").observe(0.02)
        srv = serve_ops_endpoints("test-role", port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "application/openmetrics-text; version=1.0.0")
                text = resp.read().decode()
            assert_valid_exposition(text)
            assert "# TYPE controller_reconcile_seconds histogram" in text
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz", timeout=30) as resp:
                assert json.loads(resp.read())["role"] == "test-role"
        finally:
            srv.close()

    def test_model_server_scrape(self):
        from kubeflow_tpu.serving.server import ModelServer, ServedModel

        server = ModelServer()

        def apply_fn(params, x):
            return x * params

        server.add(ServedModel(name="m", apply_fn=apply_fn, params=2.0))
        r = server.app.call("POST", "/v1/models/m:predict",
                            body={"instances": [[1.0, 2.0]]})
        assert r.status == 200
        scrape = server.app.call("GET", "/metrics")
        text = scrape.body
        assert_valid_exposition(text)
        assert 'serving_predict_total{model="m",result="success"} 1.0' in text
        assert "# TYPE serving_predict_seconds histogram" in text

    def test_mount_is_idempotent(self):
        app = App("x")
        mount_observability(app)
        n = len(list(app.iter_routes()))
        mount_observability(app)
        assert len(list(app.iter_routes())) == n

    def test_apiserver_mounts_observability(self, store):
        from kubeflow_tpu.apiserver.server import make_apiserver_app

        app = make_apiserver_app(store)
        assert app.call("GET", "/metrics").status == 200
        assert app.call("GET", "/debug/vars").body["app"] == "apiserver"


class TestDebugEndpoints:
    def _app(self):
        app = App("dbg")
        mount_observability(app)
        return app

    def test_traces_filter_by_name_and_trace_id(self):
        app = self._app()
        with TRACER.span("alpha") as a:
            pass
        with TRACER.span("beta"):
            pass
        spans = lambda r: r.body["resourceSpans"][0]["scopeSpans"][0]["spans"]  # noqa: E731
        by_name = spans(app.call("GET", "/debug/traces?name=alpha"))
        assert [s["name"] for s in by_name] == ["alpha"]
        by_id = spans(app.call("GET", f"/debug/traces?trace_id={a.trace_id}"))
        assert {s["traceId"] for s in by_id} == {a.trace_id}

    def test_traces_limit_and_bad_limit(self):
        app = self._app()
        for i in range(5):
            with TRACER.span(f"s{i}"):
                pass
        r = app.call("GET", "/debug/traces?limit=2")
        got = r.body["resourceSpans"][0]["scopeSpans"][0]["spans"]
        # most recent last, tail-limited (the dispatch span of this GET is
        # not yet finished, so only the s* spans are in the ring)
        assert [s["name"] for s in got] == ["s3", "s4"]
        assert app.call("GET", "/debug/traces?limit=nope").status == 400

    def test_otlp_shape_carries_service_name(self):
        with TRACER.span("x"):
            pass
        doc = otlp_traces(TRACER)
        attrs = doc["resourceSpans"][0]["resource"]["attributes"]
        assert {"key": "service.name",
                "value": {"stringValue": TRACER.service}} in attrs

    def test_debug_vars(self):
        app = self._app()
        v = app.call("GET", "/debug/vars").body
        assert v["threads"] >= 1 and v["pid"] > 0
        assert "uptime_seconds" in v and "gc" in v


# -- traceparent propagation --------------------------------------------------


class TestTraceparentPropagation:
    def test_two_hop_chain_one_trace(self):
        """caller → BFF app → KFAM-style downstream app: one trace id, each
        hop parented to the previous span (the dashboard→KFAM shape)."""
        bff, kfam = App("bff"), App("kfam")

        @kfam.route("/who")
        def who(req):
            return {"user": "x"}

        @bff.route("/proxy")
        def proxy(req):
            cur = TRACER.current_span()
            resp = kfam.call("GET", "/who",
                             headers={"traceparent": format_traceparent(cur)})
            return resp.body

        with TRACER.span("caller") as caller:
            resp = bff.call("GET", "/proxy",
                            headers={"traceparent": format_traceparent(caller)})
        assert resp.status == 200
        # response echoes the handler's traceparent
        assert resp.headers["traceparent"].split("-")[1] == caller.trace_id
        spans = {s.name: s for s in TRACER.finished_spans()}
        bff_span, kfam_span = spans["bff GET"], spans["kfam GET"]
        assert kfam_span.trace_id == bff_span.trace_id == caller.trace_id
        assert kfam_span.parent_span_id == bff_span.span_id
        assert bff_span.parent_span_id == caller.span_id


# -- serving engine telemetry -------------------------------------------------

CFG = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=128,
                vocab_size=101)


@pytest.fixture(scope="module")
def params():
    rng = jax.random.PRNGKey(0)
    sample = jax.random.randint(rng, (1, 8), 0, CFG.vocab_size)
    return GptLM(CFG).init(rng, sample)["params"]


class TestServingTelemetry:
    def test_request_trace_and_slo_metrics(self, params):
        from kubeflow_tpu.serving.continuous import ContinuousBatcher

        eng = ContinuousBatcher(CFG, params, slots=2, chunk=4)
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        try:
            fut = eng.submit(np.arange(8, dtype=np.int32), 6, traceparent=tp)
            assert len(fut.result(timeout=120)) == 6
        finally:
            eng.close()
        (span,) = TRACER.finished_spans(name="serving.request")
        assert span.trace_id == "ab" * 16
        assert span.parent_span_id == "cd" * 8
        assert span.status == "OK" and span.attributes["generated_tokens"] == 6
        names = [e["name"] for e in span.events]
        assert names[:4] == ["enqueued", "dequeued", "admitted", "prefill_done"]
        stamps = [e["timeUnixNano"] for e in span.events]
        assert stamps == sorted(stamps)
        assert "first_token" in names and names[-1] == "retired"
        # SLO histograms observed, exemplars carry the request's trace id
        text = METRICS.render()
        for metric in ("serving_ttft_seconds", "serving_queue_wait_seconds",
                       "serving_request_seconds", "serving_prefill_seconds",
                       "serving_inter_token_seconds"):
            assert METRICS.quantile(metric, 0.5) >= 0
            assert f"{metric}_count" in text, metric
        assert ('trace_id="' + "ab" * 16 + '"') in text
        assert METRICS.total("serving_tokens_in_total") == 8
        assert METRICS.total("serving_tokens_out_total") >= 6
        assert METRICS.value("serving_slot_occupancy") == 0.0
        assert_valid_exposition(text)

    def test_submit_after_close_error_terminates_span(self, params):
        from kubeflow_tpu.serving.continuous import ContinuousBatcher

        eng = ContinuousBatcher(CFG, params, slots=1)
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(np.arange(4, dtype=np.int32), 2)
        (span,) = TRACER.finished_spans(name="serving.request")
        assert span.status == "ERROR" and "closed" in span.status_message

    def test_predict_handler_is_trace_root(self, params):
        """The acceptance-criteria shape in-process: traceparent header →
        HTTP handler span → serving.request span, one trace."""
        from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

        model = GenerativeModel(name="gpt", apply_fn=None, params=params,
                                cfg=CFG, max_new_tokens=4)
        server = ModelServer()
        server.add(model)
        tp = "00-" + "12" * 16 + "-" + "34" * 8 + "-01"
        try:
            resp = server.app.call("POST", "/v1/models/gpt:predict",
                                   body={"instances": [[1, 2, 3]]},
                                   headers={"traceparent": tp})
            assert resp.status == 200
            assert len(resp.body["predictions"][0]) == 3 + 4
        finally:
            model.close()
        spans = TRACER.finished_spans(trace_id="12" * 16)
        by_name = {s.name: s for s in spans}
        req = by_name["serving.request"]
        handler = by_name["model-server POST"]
        assert req.parent_span_id == handler.span_id
        assert handler.parent_span_id == "34" * 8
        scrape = server.app.call("GET", "/metrics").body
        assert_valid_exposition(scrape)
        assert ('trace_id="' + "12" * 16 + '"') in scrape


# -- StepClock tracer hook ----------------------------------------------------


class TestStepClockTracing:
    def test_end_step_emits_span_with_phase_events(self):
        from kubeflow_tpu.tpu.profiling import StepClock

        clock = StepClock(tracer=TRACER)
        with clock.phase("compute"):
            pass
        with clock.fetch():
            pass
        rec = clock.end_step()
        (span,) = TRACER.finished_spans(name="train.step")
        assert span.end_ns >= span.start_ns
        assert [e["name"] for e in span.events] == ["compute", "fetch"]
        assert span.attributes["phase.total"] == pytest.approx(rec["total"], abs=1e-3)
        # next step gets a fresh window
        clock.end_step()
        assert len(TRACER.finished_spans(name="train.step")) == 2

    def test_no_tracer_no_spans(self):
        from kubeflow_tpu.tpu.profiling import StepClock

        clock = StepClock()
        with clock.compute():
            pass
        clock.end_step()
        assert TRACER.finished_spans(name="train.step") == []


def test_threaded_observe_with_spans_stays_consistent():
    """Exemplar capture + ring append under concurrency: N threads each
    observe inside their own span; totals and exposition stay coherent."""
    reg = MetricsRegistry()

    def work(i):
        with TRACER.span(f"w{i}"):
            for _ in range(50):
                reg.histogram("h", buckets=(0.5, 1.0)).observe(0.25)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.histogram("h").total == 400
    assert_valid_exposition(reg.render())
