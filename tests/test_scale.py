"""Control-plane scale observatory suite (ISSUE 11): synthetic-topology
determinism, indexed-ledger parity against the brute-force scan (the index
must be a pure accelerator — identical decisions, only faster), flight-
recorder verdict truncation, the new scheduler/workqueue/event SLIs, the
dashboard scheduler section, and the CONTROLPLANE bench-gate family.
"""

from __future__ import annotations

import importlib.util
import json
import random
import time
from pathlib import Path

import pytest

from kubeflow_tpu.api.meta import new_object
from kubeflow_tpu.apiserver.client import Client
from kubeflow_tpu.apiserver.store import Store
from kubeflow_tpu.controllers.builtin import make_tpu_node
from kubeflow_tpu.runtime.events import EventRecorder
from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.scale.topology import (
    POOL_LABEL,
    GangShape,
    synth_gangs,
    synthesize,
)
from kubeflow_tpu.scheduler.flight import (
    dominant_node_reason,
    truncate_node_verdicts,
)
from kubeflow_tpu.scheduler.ledger import ChipLedger
from kubeflow_tpu.tpu.topology import RESOURCE_TPU

ROOT = Path(__file__).resolve().parent.parent


# -- synthetic topology -------------------------------------------------------


class TestSyntheticTopology:
    def test_synthesize_is_deterministic_across_calls(self):
        a = synthesize(700, seed=3)
        b = synthesize(700, seed=3)
        assert a.pools == b.pools
        assert a.node_names() == b.node_names()
        assert synthesize(700, seed=4).pools != a.pools

    def test_node_budget_is_exact_and_every_pool_populated(self):
        topo = synthesize(997, seed=1)
        assert topo.total_nodes == 997
        assert sum(p.nodes for p in topo.pools) == 997
        assert all(p.nodes >= 1 for p in topo.pools)

    def test_nodes_carry_pool_label_selector_and_capacity(self):
        topo = synthesize(40, seed=0)
        by_pool = {p.name: p for p in topo.pools}
        for node in topo.nodes():
            labels = node["metadata"]["labels"]
            pool = by_pool[labels[POOL_LABEL]]
            assert labels["cloud.google.com/gke-nodepool"] == \
                f"tpu-{pool.generation}-pool"
            assert int(node["status"]["capacity"][RESOURCE_TPU]) == \
                pool.chips_per_node
            # the pool selector must actually match its own nodes
            assert all(labels.get(k) == v for k, v in pool.selector().items())

    def test_synth_gangs_deterministic_and_feasible(self):
        topo = synthesize(300, seed=5)
        gangs = synth_gangs(topo, 20, seed=7)
        assert gangs == synth_gangs(topo, 20, seed=7)
        by_pool = {p.name: p for p in topo.pools}
        for g in gangs:
            pool = by_pool[g.selector[POOL_LABEL]]
            assert 2 <= g.size <= max(2, min(8, pool.nodes))
            assert 1 <= g.chips_per_pod <= pool.chips_per_node


# -- indexed ledger parity ----------------------------------------------------


def _fixture_node(name: str, chips: int, labels: dict) -> dict:
    node = make_tpu_node(name, "v5e", "2x4", chips)
    node["metadata"]["labels"].update(labels)
    return node


def _bound_pod(name: str, node: str, chips: int, gang: str = "") -> dict:
    from kubeflow_tpu.scheduler.gang import POD_GROUP_LABEL

    pod = new_object("v1", "Pod", name, "default")
    if gang:
        pod["metadata"]["labels"] = {POD_GROUP_LABEL: gang}
    pod["spec"] = {
        "nodeName": node,
        "containers": [{"name": "c",
                        "resources": {"limits": {RESOURCE_TPU: str(chips)}}}],
    }
    pod["status"] = {"phase": "Running"}
    return pod


def _random_trial(rng: random.Random) -> None:
    """One randomized ledger life: nodes across pools, bound pods, churn,
    reservations — then every query must answer identically on both paths."""
    ledger = ChipLedger()
    pools = [{"pool": f"p{i}", "tier": rng.choice(["a", "b"])}
             for i in range(rng.randint(1, 4))]
    nodes = []
    for i in range(rng.randint(3, 28)):
        name = f"n{i}"
        chips = rng.choice((2, 4, 8, 16))
        ledger.on_node_event("ADDED",
                             _fixture_node(name, chips, rng.choice(pools)))
        nodes.append((name, chips))
    for i in range(rng.randint(0, 12)):  # occupancy
        name, chips = rng.choice(nodes)
        ledger.on_pod_event(
            "ADDED", _bound_pod(f"pod-{i}", name,
                                rng.randint(1, chips), gang=f"g{i % 3}"))
    if nodes and rng.random() < 0.5:  # churn: delete, maybe re-add
        name, chips = rng.choice(nodes)
        ledger.on_node_event("DELETED", {"metadata": {"name": name}})
        if rng.random() < 0.5:
            ledger.on_node_event(
                "ADDED", _fixture_node(name, chips, rng.choice(pools)))
    for g in range(rng.randint(0, 3)):  # other gangs' holds
        held = {rng.choice(nodes)[0]: rng.randint(1, 4)}
        ledger.reserve((None, f"hold{g}"), held, ttl=100.0, now=1.0)

    for q in range(10):
        reqs = []
        for _ in range(rng.randint(1, 5)):
            chips = rng.choice((0, 1, 2, 4, 8))
            sel: dict = {}
            roll = rng.random()
            if roll < 0.35:
                sel = dict(rng.choice(pools))
            elif roll < 0.5:
                sel = {"kubernetes.io/hostname": rng.choice(nodes)[0]}
            elif roll < 0.6:
                sel = {"pool": "no-such-pool"}
            reqs.append((chips, sel))
        assume = ({rng.choice(nodes)[0]: rng.randint(1, 8)}
                  if rng.random() < 0.3 else None)
        kwargs = dict(ttl=None, assume_freed=assume, now=1.0)
        got = ledger.place_and_reserve((None, f"q{q}"), reqs,
                                       use_index=True, **kwargs)
        want = ledger.place_and_reserve((None, f"q{q}"), reqs,
                                        use_index=False, **kwargs)
        assert got == want, (got, want, reqs, assume, ledger.snapshot())


class TestIndexedLedgerParity:
    def test_200_random_topologies_decide_identically(self):
        # the acceptance property: across 200 seeded random clusters the
        # indexed path returns byte-identical placements (same nodes, same
        # order) as the full scan — including infeasible (None) answers
        for trial in range(200):
            _random_trial(random.Random(f"parity:{trial}"))

    def test_index_is_default_and_override_works(self):
        ledger = ChipLedger()
        assert ledger.indexed is True
        assert ChipLedger(indexed=False).indexed is False

    def test_reservation_taken_via_index_visible_to_scan(self):
        ledger = ChipLedger()
        ledger.on_node_event("ADDED", _fixture_node("n0", 4, {"pool": "p"}))
        got = ledger.place_and_reserve((None, "g1"), [(4, {})],
                                       ttl=60.0, now=1.0)
        assert got == ["n0"]
        # the hold written by the indexed query starves the scan path too
        assert ledger.place_and_reserve((None, "g2"), [(4, {})], ttl=None,
                                        now=2.0, use_index=False) is None

    def test_explain_unaffected_by_index_choice(self):
        for indexed in (True, False):
            ledger = ChipLedger(indexed=indexed)
            ledger.on_node_event("ADDED", _fixture_node("n0", 4, {"pool": "p"}))
            ledger.on_node_event("ADDED", _fixture_node("n1", 8, {"pool": "q"}))
            ledger.reserve((None, "other"), {"n1": 8}, ttl=100.0, now=1.0)
            verdicts = ledger.explain((None, "me"),
                                      [(8, {"pool": "q"})], now=1.0)
            assert [v["reason"] for v in verdicts] == \
                ["selector_mismatch", "reserved_by_other_gang"]
            assert [v["node"] for v in verdicts] == ["n0", "n1"]

    def test_parity_at_synthesized_scale(self):
        # one non-random anchor at bench shape: a synthesized topology with
        # real gang requirement sets, indexed == scan for every gang
        topo = synthesize(400, seed=11)
        ledger = ChipLedger()
        for node in topo.nodes():
            ledger.on_node_event("ADDED", node)
        for shape in synth_gangs(topo, 16, seed=11):
            reqs = [(shape.chips_per_pod, dict(shape.selector))] * shape.size
            a = ledger.place_and_reserve((None, shape.name), reqs,
                                         ttl=None, now=1.0, use_index=True)
            b = ledger.place_and_reserve((None, shape.name), reqs,
                                         ttl=None, now=1.0, use_index=False)
            assert a == b and a is not None


# -- flight recorder truncation -----------------------------------------------


def _verdicts(n: int, reason: str = "insufficient_chips"):
    return [{"node": f"n{i}", "reason": reason, "free_chips": 0,
             "capacity": 4, "needed": 16} for i in range(n)]


class TestVerdictTruncation:
    def test_under_top_k_kept_verbatim(self):
        nodes = _verdicts(5)
        assert truncate_node_verdicts(nodes, top_k=8) == nodes

    def test_tail_collapses_to_one_summary_per_reason(self):
        nodes = _verdicts(30) + _verdicts(3, reason="selector_mismatch")
        out = truncate_node_verdicts(nodes, top_k=8)
        exact = [v for v in out if "truncated" not in v]
        summaries = [v for v in out if "truncated" in v]
        assert exact == nodes[:8]
        assert len(summaries) == 2  # one per distinct tail reason
        assert summaries[0]["reason"] == "insufficient_chips"  # biggest first
        assert summaries[0]["truncated"] == 22
        assert summaries[1]["truncated"] == 3
        assert summaries[0]["summary"] == \
            "...and 22 more nodes: insufficient_chips"
        assert sum(s["truncated"] for s in summaries) + len(exact) == 33

    def test_negative_top_k_disables_truncation(self):
        nodes = _verdicts(50)
        assert truncate_node_verdicts(nodes, top_k=-1) == nodes

    def test_dominant_reason_computed_from_full_list_stays_exact(self):
        # 9 insufficient + 1 mismatch: after truncation to top_k=2 the
        # summary still aggregates, but callers derive dominance BEFORE
        nodes = _verdicts(9) + _verdicts(1, reason="selector_mismatch")
        assert dominant_node_reason(nodes) == "insufficient_chips"
        out = truncate_node_verdicts(nodes, top_k=2)
        assert len(out) == 2 + 2

    def test_scheduler_records_truncated_decisions(self):
        from kubeflow_tpu.scheduler import SchedulerReconciler
        from kubeflow_tpu.scheduler.gang import Gang

        sched = SchedulerReconciler(verdict_top_k=4)
        gang = Gang(namespace="default", name="g", size=2, priority=0,
                    labeled=True)
        sched._record(Client(Store()), gang, [], "unschedulable",
                      "insufficient_chips", "0/40 nodes", delay=0.1,
                      nodes=_verdicts(40))
        decision = sched.flight.last_for("default/g")
        stored = decision.nodes
        assert len(stored) == 5  # 4 exact + 1 aggregated summary row
        assert stored[-1]["truncated"] == 36


# -- SLI plumbing -------------------------------------------------------------


class TestSchedulerSLIs:
    def test_cycle_rate_gauge_collected_over_window(self):
        from kubeflow_tpu.scheduler import SchedulerReconciler

        sched = SchedulerReconciler(cycles_window_s=10.0)
        now = time.monotonic()
        for _ in range(5):
            sched._cycle_times.append(now)
        sched._cycle_times.appendleft(now - 60.0)  # aged out of the window
        METRICS.render()  # scrape triggers the registered collector
        assert METRICS.value("scheduler_cycles_per_sec") == \
            pytest.approx(0.5)

    def test_bind_latency_histogram_from_member_creation(self):
        from kubeflow_tpu.apiserver.store import Store as _S
        from kubeflow_tpu.scheduler import SchedulerReconciler

        sched = SchedulerReconciler()
        member = new_object("v1", "Pod", "p0", "default")
        member["metadata"]["creationTimestamp"] = _S.now()
        sched._observe_bind_latency([member])
        _buckets, _counts, total = METRICS.histogram_counts(
            "scheduler_bind_latency_seconds")
        assert total == 1
        # sub-second bind: the observation lands in the smallest buckets
        assert (METRICS.quantile("scheduler_bind_latency_seconds", 0.99)
                or 0.0) <= 2.5

    def test_workqueue_saturation_gauge(self):
        from kubeflow_tpu.runtime.manager import Request, _WorkQueue

        q = _WorkQueue("SaturationProbe")
        METRICS.render()
        assert METRICS.value("workqueue_saturation",
                             queue="SaturationProbe") == 0.0
        for i in range(3):
            q.add(Request("default", f"item-{i}"))
        METRICS.render()
        assert METRICS.value("workqueue_saturation",
                             queue="SaturationProbe") == pytest.approx(0.75)

    def test_watch_fanout_counter_over_http(self):
        import urllib.request

        from kubeflow_tpu.apiserver.server import make_apiserver_app

        store = Store()
        app = make_apiserver_app(store)
        httpd = app.serve(0)
        try:
            base = f"http://127.0.0.1:{httpd.port}"
            Client(store).create(new_object("v1", "Pod", "w0", "default"))
            url = f"{base}/api/v1/namespaces/default/pods?watch=true&sendInitial=true"
            with urllib.request.urlopen(url, timeout=10) as resp:
                line = resp.readline()
            assert json.loads(line)["type"] in ("ADDED", "SYNC")
            assert METRICS.value("apiserver_watch_events_sent_total",
                                 resource="pods") >= 1
        finally:
            httpd.close()


class TestEventRetentionSaturation:
    def test_evicting_live_entry_increments_saturated_counter(self):
        client = Client(Store(), event_retention=2)
        rec = client.events
        assert rec.max_events == 2  # the constructor knob threads through
        for i in range(4):  # 4 distinct keys through a 2-entry cache
            obj = new_object("v1", "Pod", f"hot-{i}", "default")
            rec.emit(obj, "FailedScheduling", "m", type_="Warning")
        assert METRICS.value("events_retention_deleted_total") == 2
        # every evicted entry had JUST emitted -> all evictions are
        # saturation, the signal to raise max_events
        assert METRICS.value("events_retention_saturated_total") == 2

    def test_quiesced_eviction_is_not_saturation(self):
        client = Client(Store())
        rec = EventRecorder(client, max_events=1, live_window_s=0.0)
        for i in range(3):
            rec.emit(new_object("v1", "Pod", f"cold-{i}", "default"),
                     "Started", "m")
        assert METRICS.value("events_retention_deleted_total") == 2
        assert METRICS.value("events_retention_saturated_total") == 0


# -- dashboard scheduler section ----------------------------------------------


class TestDashboardSchedulerSection:
    def test_platform_overview_carries_scheduler_slis(self):
        from kubeflow_tpu.monitoring.plane import MonitoringPlane
        from kubeflow_tpu.monitoring.tsdb import TSDB
        from kubeflow_tpu.services.dashboard import make_dashboard_app
        from kubeflow_tpu.web.auth import AuthConfig

        db = TSDB()
        now = time.time()
        db.set_kind("scheduler_cycles_per_sec", "gauge")
        db.add_sample("scheduler_cycles_per_sec",
                      {"instance": "a:1"}, now, 12.5)
        db.set_kind("workqueue_saturation", "gauge")
        db.add_sample("workqueue_saturation",
                      {"queue": "SchedulerReconciler", "instance": "a:1"},
                      now, 0.25)
        db.set_kind("scheduler_bind_latency_seconds", "histogram")
        for ts in (now - 10, now):
            for le, cum in (("0.5", 9 if ts == now else 0),
                            ("+Inf", 10 if ts == now else 0)):
                db.add_sample("scheduler_bind_latency_seconds_bucket",
                              {"le": le, "instance": "a:1"}, ts, cum)
        app = make_dashboard_app(
            Client(Store()), auth=AuthConfig(disable_auth=True),
            monitoring=MonitoringPlane(tsdb=db))
        overview = app.call("GET", "/api/metrics/platform", None,
                            {"kubeflow-userid": "alice@example.com"})
        assert overview.status == 200
        sched = overview.body["scheduler"]
        assert sched["cyclesPerSec"] == 12.5
        assert sched["workqueueSaturation"] == {"SchedulerReconciler": 0.25}
        assert sched["bindLatencyP99"] is not None
        assert sched["bindLatencyP99"] <= 0.75  # 9/10 under the 0.5s bucket


# -- bench gate: CONTROLPLANE family ------------------------------------------


def _gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate_scale", ROOT / "tools" / "bench_gate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestControlplaneBenchFamily:
    def test_committed_round_carries_acceptance_metrics(self):
        doc = json.loads((ROOT / "CONTROLPLANE_r01.json").read_text())
        metrics = _gate().extract_metrics(doc)
        # the ISSUE 11 acceptance row: cycles/sec + bind p99 at 5k nodes,
        # with the full-scan comparison proving the >=5x index speedup
        assert metrics["scheduler_cycles_per_sec"] > 0
        assert metrics["bind_latency_p99_s"] >= 0
        assert metrics["controlplane_index_speedup_x"] >= 5.0
        assert metrics["scheduler_cycles_per_sec"] >= \
            5.0 * metrics["scheduler_cycles_per_sec_fullscan"]

    def test_load_history_merges_controlplane_family(self, tmp_path):
        gate = _gate()
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(
            {"tail": '{"metric": "a", "value": 1.0}', "parsed": None}))
        (tmp_path / "CONTROLPLANE_r01.json").write_text(json.dumps(
            {"tail": '{"metric": "scheduler_cycles_per_sec", "value": 9.0}',
             "parsed": None}))
        (tmp_path / "NOTAFAMILY_r01.json").write_text("{}")
        rounds = gate.load_history(tmp_path, [])
        assert rounds == {1: {"a": 1.0, "scheduler_cycles_per_sec": 9.0}}

    def test_gate_specs_direction_for_new_metrics(self):
        gate = _gate()
        assert gate.spec_for("scheduler_cycles_per_sec")[0] == "higher"
        assert gate.spec_for("bind_latency_p99_s")[0] == "lower"
        assert gate.spec_for("apiserver_list_p99_ms_storm")[0] == "lower"

    def test_full_history_still_gates_green_when_r05_waived(
            self, bench_history):
        gate = _gate()
        rounds = gate.load_history(bench_history, [])
        assert 1 in rounds and "scheduler_cycles_per_sec" in rounds[1]
        _results, rc = gate.gate(rounds, waivers=[
            "serving_bert_p50_ms_b8@r05",
            "serving_decode_tokens_per_sec_b8@r05",
            "serving_gpt_kv_decode_tokens_per_sec_b8@r05",
        ])
        assert rc == 0
