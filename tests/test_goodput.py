"""Goodput ledger (ISSUE 19): the honesty contract (fractions sum to
exactly 1.0, named buckets reconstruct wallclock), replay attribution
across scripted incarnations, StepClock compile/data-wait draining, the
ElasticTrainer integration (per-incarnation goodput sections, urgent-save
vs lost-gang replay), per-tenant chip metering (informer-echo idempotence,
accrual across preemption, scrape-time flush), cold-start histogram
lifecycle (in-process and through the real gang scheduler), the
``checkpoint_restore_seconds`` satellite, and the serving goodput view +
``/debug/goodput`` surface."""

import numpy as np
import pytest

from kubeflow_tpu.controllers.builtin import PodletReconciler, make_tpu_node
from kubeflow_tpu.monitoring.goodput import (
    BADPUT_BUCKETS,
    GoodputLedger,
    TenantChipMeter,
    debug_goodput,
    goodput_recording_rules,
    serving_goodput_view,
)
from kubeflow_tpu.monitoring.tsdb import TSDB
from kubeflow_tpu.runtime.manager import Manager
from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.scheduler import SchedulerReconciler
from kubeflow_tpu.training.checkpoint import SAVE_BUCKETS, Checkpointer
from kubeflow_tpu.training.elastic import ElasticTrainer, SliceOffer


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now

    def tick(self, dt: float) -> float:
        self.now += dt
        return self.now


# -- the honesty contract ------------------------------------------------------


class TestGoodputLedger:
    def test_fractions_sum_to_exactly_one_and_reconcile(self):
        clk = FakeClock()
        led = GoodputLedger("t1", clock=clk)
        led.start()
        led.begin_incarnation(0)
        clk.tick(2.0)
        led.note("scheduling_wait", 2.0)
        clk.tick(1.5)
        led.note("checkpoint_restore", 1.5)
        for i in range(4):
            clk.tick(1.0)
            led.step(i, 1.0)
        clk.tick(0.5)
        led.note("checkpoint_save", 0.5)
        led.end_incarnation("completed", 4)
        snap = led.finish()

        assert sum(snap["fractions"].values()) == 1.0
        assert snap["reconstructionError"] == 0.0
        assert snap["wallclockSeconds"] == pytest.approx(8.0)
        assert snap["goodputSeconds"] == pytest.approx(4.0)
        assert snap["badputSeconds"]["scheduling_wait"] == pytest.approx(2.0)
        assert snap["badputSeconds"]["checkpoint_restore"] == pytest.approx(1.5)
        assert snap["badputSeconds"]["checkpoint_save"] == pytest.approx(0.5)
        assert set(snap["badputSeconds"]) == set(BADPUT_BUCKETS)
        # the counters carry the same story as the snapshot
        assert METRICS.value("training_badput_seconds_total",
                             bucket="scheduling_wait") == pytest.approx(2.0)
        assert METRICS.total("training_goodput_seconds_total") == pytest.approx(4.0)
        assert METRICS.value("training_goodput_fraction",
                             workload="t1") == pytest.approx(0.5)

    def test_unmeasured_time_lands_in_other_not_a_named_bucket(self):
        clk = FakeClock()
        led = GoodputLedger("t2", clock=clk)
        led.start()
        led.begin_incarnation(0)
        clk.tick(4.0)
        led.step(0, 1.0)  # 3s of wallclock nobody measured
        snap = led.finish()
        assert snap["badputSeconds"]["other"] == pytest.approx(3.0)
        assert sum(snap["fractions"].values()) == 1.0
        assert snap["reconstructionError"] == pytest.approx(3.0 / 4.0)

    def test_replay_attribution_across_scripted_incarnations(self):
        clk = FakeClock()
        led = GoodputLedger("t3", clock=clk)
        led.start()
        led.begin_incarnation(0)
        for i in range(5):  # steps 0..4, then the gang dies
            clk.tick(1.0)
            led.step(i, 1.0)
        led.end_incarnation("lost", 4)
        led.begin_incarnation(1)
        for i in range(3, 8):  # restored at step 3: 3 and 4 are replay
            clk.tick(1.0)
            led.step(i, 1.0)
        section = led.end_incarnation("completed", 8)
        snap = led.finish()

        assert section["replaySteps"] == 2
        assert snap["badputSeconds"]["preemption_replay"] == pytest.approx(2.0)
        assert snap["goodputSeconds"] == pytest.approx(8.0)
        assert snap["incarnations"][0]["goodputSeconds"] == pytest.approx(5.0)
        assert METRICS.value("training_badput_seconds_total",
                             bucket="preemption_replay") == pytest.approx(2.0)

    def test_step_clock_compile_and_data_wait_drain(self):
        class FakeStepClock:
            compile_s = 0.0
            steps: list = []

        sc = FakeStepClock()
        clk = FakeClock()
        led = GoodputLedger("t4", clock=clk)
        led.start()
        led.attach_step_clock(sc)
        led.begin_incarnation(0)
        # step 0: 2s compile + 0.5s data wait inside a 3s step
        sc.compile_s = 2.0
        sc.steps = [{"data_wait": 0.5, "compute": 0.4, "total": 1.0}]
        clk.tick(3.0)
        led.step(0, 3.0)
        # step 1: no new compile, no new clock records
        clk.tick(1.0)
        led.step(1, 1.0)
        snap = led.finish()

        assert snap["badputSeconds"]["compile"] == pytest.approx(2.0)
        assert snap["badputSeconds"]["data_wait"] == pytest.approx(0.5)
        assert snap["goodputSeconds"] == pytest.approx(1.5)
        assert snap["reconstructionError"] == 0.0

    def test_attach_ignores_preexisting_clock_history(self):
        class FakeStepClock:
            compile_s = 5.0
            steps = [{"data_wait": 9.0}]

        clk = FakeClock()
        led = GoodputLedger("t5", clock=clk)
        led.start()
        led.attach_step_clock(FakeStepClock())
        led.begin_incarnation(0)
        clk.tick(1.0)
        led.step(0, 1.0)
        snap = led.finish()
        assert snap["badputSeconds"]["compile"] == 0.0
        assert snap["badputSeconds"]["data_wait"] == 0.0
        assert snap["goodputSeconds"] == pytest.approx(1.0)

    def test_note_rejects_unknown_bucket(self):
        led = GoodputLedger("t6", clock=FakeClock())
        with pytest.raises(ValueError, match="unknown badput bucket"):
            led.note("coffee_break", 1.0)
        with pytest.raises(ValueError):
            led.note("other", 1.0)  # the residual is computed, never written

    def test_gauge_refreshes_at_render_time(self):
        clk = FakeClock()
        led = GoodputLedger("t7", clock=clk)
        led.start()
        led.begin_incarnation(0)
        clk.tick(1.0)
        led.step(0, 1.0)
        # no finish(): the collector must surface the live fraction
        METRICS.render()
        assert METRICS.value("training_goodput_fraction",
                             workload="t7") == pytest.approx(1.0)


# -- ElasticTrainer integration ------------------------------------------------


class TinyWorkload:
    def init(self, offer):
        return {"x": np.zeros(4), "offer": offer}

    def restore(self, offer, snap, meta):
        return {"x": np.asarray(snap["x"]), "offer": offer}

    def snapshot(self, state):
        return {"x": np.asarray(state["x"])}, {}

    def run_step(self, state, step):
        state["x"] = state["x"] + 1
        return state, float(step)


class ScriptedHandler:
    """check() verdicts by step count: 'ok' until ``at``, then ``verdict``."""

    def __init__(self, verdict: str, at: int):
        self.verdict = verdict
        self.at = at
        self.calls = 0
        self.acked = None

    def check(self):
        from kubeflow_tpu.training.elastic import DrainStatus

        verdict = self.verdict if self.calls >= self.at else "ok"
        self.calls += 1
        return DrainStatus(verdict)

    def ack(self, step):
        self.acked = step


class TestElasticTrainerGoodput:
    def _trainer(self, tmp_path, handlers, total=8, every=3):
        it = iter(handlers)
        return ElasticTrainer(
            TinyWorkload(),
            Checkpointer(str(tmp_path), max_to_keep=3),
            lambda attempt: SliceOffer(devices=[object()] * 2),
            total,
            checkpoint_every=every,
            handler_factory=lambda offer: next(it),
        )

    def test_lost_gang_replays_into_the_ledger(self, tmp_path):
        # attempt 0: periodic save at step 2, gang LOST at step 4 (no urgent
        # save) → attempt 1 restores step 2, replays 3 and 4
        trainer = self._trainer(
            tmp_path, [ScriptedHandler("lost", at=4),
                       ScriptedHandler("ok", at=99)])
        report = trainer.run()
        assert report.completed
        assert [i["outcome"] for i in report.incarnations] == [
            "lost", "completed"]
        assert report.incarnations[0]["goodput"]["replaySteps"] == 0
        assert report.incarnations[1]["goodput"]["replaySteps"] == 2
        snap = trainer.goodput.snapshot()
        assert snap["badputSeconds"]["preemption_replay"] > 0.0
        assert snap["badputSeconds"]["checkpoint_restore"] > 0.0
        assert snap["badputSeconds"]["checkpoint_save"] > 0.0
        # wallclock-derived floats: the ledger's residual closes the sum to
        # the last bit or two, which of the two is rounding's business
        assert sum(snap["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
        assert METRICS.histogram("checkpoint_restore_seconds").total == 1

    def test_graceful_drain_has_zero_replay(self, tmp_path):
        handler = ScriptedHandler("draining", at=4)
        trainer = self._trainer(
            tmp_path, [handler, ScriptedHandler("ok", at=99)])
        report = trainer.run()
        assert report.completed
        assert report.preemptions_survived == 1
        assert handler.acked == 4  # urgent save covered the drained step
        first, second = report.incarnations
        assert second["startStep"] == first["endStep"] + 1
        assert second["goodput"]["replaySteps"] == 0
        snap = trainer.goodput.snapshot()
        assert snap["badputSeconds"]["preemption_replay"] == 0.0
        assert snap["incarnations"][0]["outcome"] == "preempted"
        # every incarnation carries its goodput section in the metadata
        assert all("goodput" in i for i in report.incarnations)
        assert METRICS.value("training_goodput_fraction",
                             workload="training") > 0.0


# -- tenant chip metering ------------------------------------------------------


class TestTenantChipMeter:
    def _meter(self):
        clk = FakeClock()
        return TenantChipMeter(clock=clk, collector_key=None), clk

    def test_accrues_chips_times_bound_duration(self):
        meter, clk = self._meter()
        meter.on_bind(("ns-a", "pod-0"), "ns-a", 4)
        clk.tick(10.0)
        meter.on_unbind(("ns-a", "pod-0"))
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(40.0)

    def test_informer_echo_replay_is_idempotent(self):
        meter, clk = self._meter()
        key = ("ns-a", "pod-0")
        meter.on_bind(key, "ns-a", 4)
        clk.tick(5.0)
        meter.on_bind(key, "ns-a", 4)  # the echo of an assumed bind
        clk.tick(5.0)
        meter.on_unbind(key)
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(40.0)

    def test_accrual_continues_across_preemption(self):
        meter, clk = self._meter()
        meter.on_bind(("ns-a", "pod-0"), "ns-a", 8)
        clk.tick(3.0)
        meter.on_unbind(("ns-a", "pod-0"))  # preempted
        clk.tick(60.0)  # unbound: no accrual while waiting for chips
        meter.on_bind(("ns-a", "pod-0-re"), "ns-a", 8)
        clk.tick(2.0)
        meter.on_unbind(("ns-a", "pod-0-re"))
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(40.0)

    def test_flush_settles_open_intervals_incrementally(self):
        meter, clk = self._meter()
        meter.on_bind(("ns-a", "pod-0"), "ns-a", 2)
        clk.tick(5.0)
        meter.flush()  # scrape-time: counter must already see 10 chip-s
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(10.0)
        clk.tick(5.0)
        meter.on_unbind(("ns-a", "pod-0"))
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(20.0)
        assert meter.open_intervals() == {}

    def test_rebind_with_changed_chips_settles_then_reopens(self):
        meter, clk = self._meter()
        key = ("ns-a", "pod-0")
        meter.on_bind(key, "ns-a", 4)
        clk.tick(10.0)
        meter.on_bind(key, "ns-a", 8)  # record changed: 40 settled, reopen
        clk.tick(10.0)
        meter.on_unbind(key)
        assert METRICS.value("tenant_chip_seconds_total",
                             namespace="ns-a") == pytest.approx(120.0)

    def test_ledger_feeds_the_process_meter(self, client):
        from kubeflow_tpu.api.meta import new_object
        from kubeflow_tpu.monitoring.goodput import TENANT_METER
        from kubeflow_tpu.scheduler.ledger import ChipLedger

        ledger = ChipLedger()
        pod = new_object(
            "v1", "Pod", "w-0", "team-a",
            spec={"nodeName": "n0", "containers": [{
                "name": "c", "resources": {
                    "limits": {"google.com/tpu": "4"}}}]},
        )
        ledger.on_pod_event("ADDED", pod)
        assert TENANT_METER.open_intervals().get("team-a") == 4
        ledger.on_pod_event("DELETED", pod)
        assert "team-a" not in TENANT_METER.open_intervals()


# -- cold-start histogram ------------------------------------------------------


class TestColdStart:
    def test_clientless_replica_observes_on_creation(self):
        from tests.test_fleet import fake_fleet

        fleet = fake_fleet(2, name="cs")
        try:
            hist = METRICS.histogram("fleet_replica_cold_start_seconds")
            assert hist.total == 2
            assert hist.sum < 5.0  # in-process fakes are routable instantly
        finally:
            fleet.close()

    def test_scheduled_replica_observes_on_bind_and_after_preemption(self):
        from kubeflow_tpu.api.meta import new_object
        from kubeflow_tpu.scheduler.gang import (POD_GROUP_LABEL,
                                                 POD_GROUP_SIZE_ANNOTATION)
        from kubeflow_tpu.serving.fleet import EngineFleet
        from tests.test_fleet import FakeEngine, wait_for

        mgr = Manager()
        mgr.add(SchedulerReconciler(assembly_timeout=5.0, reservation_ttl=5.0,
                                    backoff_base=0.02, backoff_cap=0.5))
        mgr.add(PodletReconciler())
        mgr.client.create(make_tpu_node("tpu-node-0", "v5e", "2x4", 4))
        mgr.start()
        fleet = EngineFleet(replicas=1, min_replicas=1, max_replicas=2,
                            name="srv", engine_factory=FakeEngine,
                            client=mgr.client, replica_chips=4,
                            priority_class="trial", poll_interval=0.05,
                            register_debug=False)
        try:
            assert fleet.wait_ready(1, timeout=10)
            hist = METRICS.histogram("fleet_replica_cold_start_seconds")
            assert hist.total == 1  # bind, not creation, made it routable
            first_cold_start = hist.sum
            assert first_cold_start > 0.0

            # preemption: the replacement replica's pod waits for chips, so
            # its cold start spans the whole eviction+rebind cycle
            old_engine = fleet.live_handles()[0].engine
            mgr.client.create(new_object(
                "v1", "Pod", "urgent-0", "default",
                labels={POD_GROUP_LABEL: "urgent"},
                annotations={POD_GROUP_SIZE_ANNOTATION: "1"},
                spec={"priorityClassName": "system",
                      "containers": [{"name": "c", "resources": {
                          "limits": {"google.com/tpu": "4"}}}]}))
            wait_for(lambda: old_engine.drained, timeout=15.0,
                     desc="preempted replica drained")
            mgr.client.delete_opt("v1", "Pod", "urgent-0", "default")
            wait_for(lambda: fleet.wait_ready(1, timeout=0.1), timeout=15.0,
                     desc="replacement replica routable")
            hist = METRICS.histogram("fleet_replica_cold_start_seconds")
            assert hist.total == 2
            assert hist.sum > first_cold_start
        finally:
            fleet.close()
            mgr.stop()


# -- checkpoint_restore_seconds ------------------------------------------------


class TestCheckpointRestoreHistogram:
    def test_observed_only_on_successful_restore(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path))
        with pytest.raises(FileNotFoundError):
            ckpt.restore_numpy()
        hist = METRICS.histogram("checkpoint_restore_seconds",
                                 buckets=SAVE_BUCKETS)
        assert hist.total == 0

        ckpt.save(0, {"x": np.arange(4.0)}, meta={"step": 0})
        tree, meta = ckpt.restore_numpy()
        np.testing.assert_array_equal(tree["x"], np.arange(4.0))
        assert METRICS.histogram("checkpoint_restore_seconds").total == 1

        restored = ckpt.restore({"x": np.zeros(4)})
        np.testing.assert_array_equal(restored["x"], np.arange(4.0))
        assert METRICS.histogram("checkpoint_restore_seconds").total == 2
        assert METRICS.histogram("checkpoint_save_seconds").total == 1


# -- serving goodput view + surfaces -------------------------------------------


class TestServingGoodputView:
    def test_token_goodput_fraction_from_waste_counters(self):
        METRICS.counter("serving_tokens_out_total").inc(90)
        METRICS.counter("serving_discarded_tail_tokens_total").inc(10)
        METRICS.counter("serving_wasted_decode_tokens_total").inc(6)
        view = serving_goodput_view()
        assert view["tokenGoodputFraction"] == pytest.approx(0.9)
        assert view["deliveredTokens"] == 90
        assert view["wastedDecodeTokens"] == 6

    def test_empty_registry_reports_no_fraction(self):
        assert serving_goodput_view()["tokenGoodputFraction"] is None

    def test_fleet_submit_meters_tenant_tokens(self):
        from tests.test_fleet import fake_fleet, prompt

        fleet = fake_fleet(1, name="tok")
        try:
            fleet.submit(prompt(3, n=6), 4)
            assert METRICS.value("tenant_tokens_total", namespace="default",
                                 direction="in") == 6.0
            assert METRICS.value("tenant_tokens_total", namespace="default",
                                 direction="out") == 4.0
        finally:
            fleet.close()

    def test_debug_goodput_served_over_observability(self):
        from kubeflow_tpu.runtime.obs import mount_observability
        from kubeflow_tpu.web.http import App

        clk = FakeClock()
        led = GoodputLedger("dbg", clock=clk)
        led.start()
        led.begin_incarnation(0)
        clk.tick(1.0)
        led.step(0, 1.0)
        led.finish()

        app = App("test")
        mount_observability(app)
        resp = app.call("GET", "/debug/goodput", None, {})
        assert resp.status == 200, resp.body
        doc = resp.body
        assert "dbg" in doc["workloads"]
        assert sum(doc["workloads"]["dbg"]["fractions"].values()) == 1.0
        assert "serving" in doc and "tenants" in doc
        # and directly, for the handler contract
        assert debug_goodput()["workloads"]["dbg"]["goodputFraction"] == 1.0


class TestGoodputRecordingRule:
    def test_measured_fraction_from_federated_counters(self):
        tsdb = TSDB()
        tsdb.add_sample("training_goodput_seconds_total",
                        {"instance": "a"}, 100.0, 30.0)
        tsdb.add_sample("training_badput_seconds_total",
                        {"instance": "a", "bucket": "compile"}, 100.0, 5.0)
        tsdb.add_sample("training_badput_seconds_total",
                        {"instance": "a", "bucket": "preemption_replay"},
                        100.0, 5.0)
        (rule,) = goodput_recording_rules()
        assert rule.record == "platform:training_goodput_fraction"
        results = list(rule.fn(tsdb, 101.0))
        assert results == [({}, pytest.approx(0.75))]

    def test_rule_is_silent_with_no_data(self):
        (rule,) = goodput_recording_rules()
        assert list(rule.fn(TSDB(), 0.0)) == []
