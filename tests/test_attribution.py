"""training/attribution.py (per-module pricing, roofline verdicts, step
decomposition) and tools/bench_gate.py (the committed-history regression
gate)."""

from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.tpu.profiling import StepClock
from kubeflow_tpu.training.attribution import (
    TRAIN_STEP_FACTOR,
    attribute_gpt,
    attribute_resnet,
    attribution_report,
    price_callable,
    record_step_peak_hbm,
)

ROOT = Path(__file__).resolve().parent.parent


# -- price_callable -----------------------------------------------------------

class TestPriceCallable:
    def test_prices_from_structs_without_allocating(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        cost = price_callable(lambda x, y: x @ y, a, b, name="mm")
        # one [64,128]@[128,32] = 2*64*128*32 forward flops, x train factor
        assert cost.flops == pytest.approx(
            2 * 64 * 128 * 32 * TRAIN_STEP_FACTOR, rel=0.01)
        assert cost.hbm_bytes > 0
        assert cost.verdict in ("compute-bound", "hbm-bound")
        assert cost.est_seconds > 0
        assert cost.peak_hbm_bytes > 0

    def test_count_scales_all_applications(self):
        a = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        one = price_callable(lambda x: x @ x, a, name="sq", count=1)
        four = price_callable(lambda x: x @ x, a, name="sq", count=4)
        assert four.flops == pytest.approx(4 * one.flops)
        assert four.hbm_bytes == pytest.approx(4 * one.hbm_bytes)

    def test_roofline_classification_tracks_intensity(self):
        # big square matmul: high arithmetic intensity -> compute-bound
        # (f32: the CPU backend charges bf16 matmuls extra conversion bytes)
        big = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
        mm = price_callable(lambda x, y: x @ y, big, big, name="big_mm")
        assert mm.verdict == "compute-bound"
        # elementwise add: one flop per 12 bytes -> hbm-bound everywhere
        vec = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
        add = price_callable(lambda x, y: x + y, vec, vec, name="add")
        assert add.verdict == "hbm-bound"
        assert mm.intensity > add.intensity


# -- ResNet-50 walk (the acceptance-criteria report) --------------------------

@pytest.fixture(scope="module")
def resnet_costs():
    return attribute_resnet(batch=1, image=224, generation="v5e")


class TestResNetAttribution:
    def test_walk_covers_the_whole_model(self, resnet_costs):
        names = [c.name for c in resnet_costs]
        assert names[0] == "stem" and names[-1] == "classifier_head"
        blocks = [n for n in names if n.startswith("stage")]
        assert len(blocks) == 16  # ResNet-50: 3 + 4 + 6 + 3
        assert "stage2_block1" in blocks and "stage4_block3" in blocks

    def test_fused_set_matches_the_model_predicate(self, resnet_costs):
        # the model's own predicates (_fusable + _fusable_transition, padded
        # tiling + the transition kernel) admit ALL 16 blocks at 224x224 —
        # attribution must report the truth, which is the whole point
        fused = {c.name for c in resnet_costs if c.fused}
        assert fused == {c.name for c in resnet_costs
                         if c.name.startswith("stage")}
        assert len(fused) == 16

    def test_every_block_is_priced_with_flops_bytes_and_verdict(self, resnet_costs):
        for c in resnet_costs:
            assert c.flops > 0, c.name
            assert c.hbm_bytes > 0, c.name
            assert c.peak_hbm_bytes > 0, c.name
            assert c.verdict in ("compute-bound", "hbm-bound"), c.name

    def test_only_stem_and_head_remain_unfused(self, resnet_costs):
        # full coverage: every bottleneck runs a fused kernel, so the only
        # unfused sinks left are the stem and the classifier head — and the
        # former downsampling blocks now lead the FUSED sink table
        report = attribution_report(resnet_costs, step_seconds=0.1,
                                    generation="v5e")
        unfused = report.top_sinks(6, fused=False)
        assert {c.name for c in unfused} == {"stem", "classifier_head"}
        top_fused = report.top_sinks(6, fused=True)
        assert any("transition" in c.detail for c in top_fused)

    def test_coverage_counts_fused_bottlenecks(self, resnet_costs):
        report = attribution_report(resnet_costs, step_seconds=0.1,
                                    generation="v5e")
        assert report.coverage() == {"fused": 16, "total": 16}

    def test_projection_blocks_are_labeled(self, resnet_costs):
        by_name = {c.name: c for c in resnet_costs}
        assert by_name["stage1_block1"].detail == "projection/transition"
        for stage in (2, 3, 4):
            assert (by_name[f"stage{stage}_block1"].detail
                    == "strided+projection/transition")
        assert by_name["stage3_block2"].detail == "identity"


# -- GPT walk -----------------------------------------------------------------

def test_gpt_walk_counts_the_scanned_stack():
    from kubeflow_tpu.models.gpt import GptConfig

    cfg = GptConfig(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    d_ff=128, max_seq=32)
    costs = attribute_gpt(cfg, batch=2, seq=32, generation="v5e")
    block = next(c for c in costs if c.kind == "gpt_block")
    assert block.count == 3
    one_layer = block.flops / block.count
    assert one_layer > 0
    head = next(c for c in costs if c.kind == "loss_head")
    assert head.fused and head.detail == "blockwise"
    unfused = attribute_gpt(cfg, batch=2, seq=32, fused_loss=False,
                            generation="v5e")
    assert not next(c for c in unfused if c.kind == "loss_head").fused


# -- report: fractions decompose the MEASURED step ----------------------------

class TestAttributionReport:
    def _clock(self, steps=3):
        clock = StepClock()
        for _ in range(steps):
            with clock.data_wait():
                time.sleep(0.002)
            with clock.compute():
                time.sleep(0.004)
            with clock.fetch():
                time.sleep(0.001)
            clock.end_step()
        return clock

    def test_fractions_sum_to_one_and_match_the_clock(self, resnet_costs):
        clock = self._clock()
        report = attribution_report(resnet_costs, clock=clock,
                                    generation="v5e")
        assert sum(report.fractions.values()) == pytest.approx(1.0)
        # the decomposition must reconstruct the measured step within 5%
        reconstructed = report.step_seconds * sum(report.fractions.values())
        assert reconstructed == pytest.approx(report.step_seconds, rel=0.05)
        assert report.step_seconds == pytest.approx(
            clock.summary()["total"], rel=1e-6)
        # fused vs unfused split follows the roofline estimates: with all 16
        # bottlenecks fused, only the stem + head remain unfused
        assert report.fractions["fused_compute"] > report.fractions["unfused_compute"] > 0

    def test_steps_per_record_normalizes_bench_windows(self, resnet_costs):
        clock = self._clock(steps=2)
        whole = attribution_report(resnet_costs, clock=clock,
                                   generation="v5e")
        per_10 = attribution_report(resnet_costs, clock=clock,
                                    steps_per_record=10, generation="v5e")
        assert per_10.step_seconds == pytest.approx(whole.step_seconds / 10)

    def test_render_and_to_dict(self, resnet_costs):
        report = attribution_report(resnet_costs, step_seconds=0.05,
                                    generation="v5e")
        text = report.render(top_n=5)
        assert "Attribution report (v5e" in text
        assert "strided+projection" in text
        d = json.loads(json.dumps(report.to_dict()))
        assert d["modules"] == len(resnet_costs)
        assert d["fused_modules"] == 16
        assert d["coverage"] == {"fused": 16, "total": 16}
        # only stem + classifier_head are left unfused
        assert len(d["top_unfused_sinks"]) == 2
        assert all(s["verdict"] for s in d["top_unfused_sinks"])
        assert len(d["top_fused_sinks"]) == 5
        assert "fused coverage: 16/16" in report.render()

    def test_without_clock_everything_is_unfused_compute(self):
        report = attribution_report([], step_seconds=0.2, generation="v5e")
        assert report.fractions == {"data_wait": 0.0, "fused_compute": 0.0,
                                    "unfused_compute": 1.0, "other": 0.0}


def test_record_step_peak_hbm_publishes_gauges():
    from kubeflow_tpu.runtime.metrics import METRICS

    mem = {"peak_hbm_bytes": 1234, "argument_bytes": 1000,
           "output_bytes": 200, "temp_bytes": 34}
    assert record_step_peak_hbm(mem) == 1234
    text = METRICS.render()
    assert "training_step_peak_hbm_bytes 1234" in text
    assert 'training_step_hbm_bytes{component="temp"} 34' in text
    assert record_step_peak_hbm(None) is None


def test_memory_stats_from_a_compiled_executable():
    from kubeflow_tpu.training.flops import memory_stats

    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    mem = memory_stats(compiled)
    assert mem is not None
    assert mem["peak_hbm_bytes"] == sum(
        v for k, v in mem.items() if k != "peak_hbm_bytes")
    assert mem["argument_bytes"] >= 32 * 32 * 4


# -- bench_gate ---------------------------------------------------------------

def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", ROOT / "tools" / "bench_gate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gate_mod():
    return _load_gate()


class TestBenchGate:
    def test_r05_flags_the_serving_regressions(self, gate_mod, bench_history):
        # with r06 (the paged-KV recovery round), r07 (the autotuner round)
        # and r08 (the disaggregated-serving round) excluded, the history
        # ends at r05 and the gate must still retroactively flag the
        # r04->r05 slide
        rounds = gate_mod.load_history(bench_history, ["r06", "r07", "r08"])
        results, rc = gate_mod.gate(rounds)
        assert rc == 1
        fails = {r["metric"] for r in results if r["verdict"] == "FAIL"}
        assert "serving_decode_tokens_per_sec_b8" in fails
        assert "serving_bert_p50_ms_b8" in fails
        # training metrics sit inside their noise band and must NOT flag
        oks = {r["metric"]: r["verdict"] for r in results}
        assert oks["resnet50_train_mfu"] in ("OK", "IMPROVED")
        assert oks["hpo_trials_per_hour"] == "OK"

    def test_r06_recovers_without_waivers(self, gate_mod, bench_history):
        # the r06 round beats the r04 serving numbers outright, so the
        # history rewound to r06 gates green with zero waivers
        rounds = gate_mod.load_history(bench_history, ["r07", "r08"])
        results, rc = gate_mod.gate(rounds)
        assert rc == 0
        assert max(rounds) == 6
        verdicts = {r["metric"]: r["verdict"] for r in results}
        assert verdicts["serving_decode_tokens_per_sec_b8"] == "IMPROVED"
        assert verdicts["serving_bert_p50_ms_b8"] == "IMPROVED"
        # the new SLI rows enter as baselines (no earlier round carries them)
        assert verdicts["serving_ttft_p99_s"] == "BASELINE"
        assert verdicts["spec_accept_rate"] == "BASELINE"

    def test_r07_breaks_the_training_plateau(self, gate_mod, bench_history):
        # rewound to r07, the history gates green with zero waivers, and the
        # autotuner round clears the new absolute flagship floors outright
        rounds = gate_mod.load_history(bench_history, ["r08"])
        results, rc = gate_mod.gate(rounds)
        assert rc == 0
        assert max(rounds) == 7
        by = {r["metric"]: r for r in results}
        assert by["resnet50_train_mfu"]["verdict"] == "IMPROVED"
        assert by["resnet50_train_mfu"]["value"] >= 40.0
        assert by["gpt2_medium_mfu_pct"]["verdict"] == "IMPROVED"
        assert by["gpt2_medium_mfu_pct"]["value"] >= 50.0
        # the flagship floors are active at r07 and not breached
        for metric in ("resnet50_train_mfu", "gpt2_medium_mfu_pct",
                       "gpt2_medium_tokens_per_sec", "images_per_sec_per_chip"):
            assert by[metric]["floor"] == gate_mod.FLOORS[metric][0]
            assert by[metric]["floor_breached"] is False

    def test_r08_disagg_round_gates_green(self, gate_mod, bench_history):
        # the full history gates green with zero waivers: the disaggregated
        # round's heterogeneous-mix SLIs enter as baselines, and the
        # distilled draft clears the new spec_accept_rate floor outright
        rounds = gate_mod.load_history(bench_history, [])
        results, rc = gate_mod.gate(rounds)
        assert rc == 0
        assert max(rounds) == 8
        by = {r["metric"]: r for r in results}
        assert by["decode_tok_s_heterogeneous"]["verdict"] == "BASELINE"
        assert by["kv_handoff_p99_s"]["verdict"] == "BASELINE"
        assert by["spec_accept_rate"]["verdict"] == "IMPROVED"
        assert by["spec_accept_rate"]["value"] >= 0.5
        assert by["spec_accept_rate"]["floor"] == gate_mod.FLOORS[
            "spec_accept_rate"][0]
        assert by["spec_accept_rate"]["floor_breached"] is False

    def test_excluding_r05_passes(self, gate_mod, bench_history):
        rounds = gate_mod.load_history(
            bench_history, ["r05", "r06", "r07", "r08"])
        results, rc = gate_mod.gate(rounds)
        assert rc == 0
        assert max(rounds) == 4
        # r04's resnet dip (-7.6%) is inside the 10% band
        resnet = next(r for r in results if r["metric"] == "resnet50_train_mfu")
        assert resnet["verdict"] == "OK"
        # gpt/serving/hpo first appear in r04: baseline, not a verdict
        gpt = next(r for r in results if r["metric"] == "gpt2_medium_mfu_pct")
        assert gpt["verdict"] == "BASELINE"

    def test_waivers_turn_known_fails_green(self, gate_mod, bench_history):
        rounds = gate_mod.load_history(bench_history, ["r06", "r07", "r08"])
        waivers = [f"{m}@r05" for m in (
            "serving_bert_p50_ms_b8",
            "serving_decode_tokens_per_sec_b8",
            "serving_gpt_kv_decode_tokens_per_sec_b8")]
        results, rc = gate_mod.gate(rounds, waivers)
        assert rc == 0
        assert {r["metric"] for r in results if r["verdict"] == "WAIVED"} \
            == set(w.split("@")[0] for w in waivers)

    def test_waiver_dies_with_the_next_round(self, gate_mod):
        rounds = {4: {"serving_bert_p50_ms_b8": 96.1},
                  5: {"serving_bert_p50_ms_b8": 105.1},
                  6: {"serving_bert_p50_ms_b8": 115.0}}
        _, rc = gate_mod.gate(rounds, ["serving_bert_p50_ms_b8@r05"])
        assert rc == 1, "an r05 waiver must not excuse an r06 regression"

    def test_direction_lower_is_better(self, gate_mod):
        rounds = {1: {"x_p99_ms": 10.0}, 2: {"x_p99_ms": 12.0}}
        results, rc = gate_mod.gate(rounds)
        assert rc == 1 and results[0]["verdict"] == "FAIL"
        rounds = {1: {"x_p99_ms": 10.0}, 2: {"x_p99_ms": 9.0}}
        results, rc = gate_mod.gate(rounds)
        assert rc == 0 and results[0]["verdict"] == "IMPROVED"

    def test_best_so_far_not_just_previous_round(self, gate_mod):
        # a slow two-round slide past tolerance must flag even though each
        # single hop is within tolerance of its predecessor
        rounds = {1: {"m_tokens_per_sec": 100.0},
                  2: {"m_tokens_per_sec": 94.0},
                  3: {"m_tokens_per_sec": 88.0}}
        results, rc = gate_mod.gate(rounds)
        assert rc == 1 and results[0]["best_round"] == 1

    def test_error_rows_never_count(self, gate_mod):
        doc = {"tail": '{"metric": "m", "value": 0.0, "error": "boom"}\n'
                       '{"metric": "m2", "value": 5.0}',
               "parsed": {"metric": "sum", "value": 1.0, "errors": {"m": "boom"}}}
        metrics = gate_mod.extract_metrics(doc)
        assert metrics == {"m2": 5.0}

    def test_truncated_first_tail_line_is_skipped(self, gate_mod):
        doc = {"tail": 'alue": 30.5, "unit": "percent_mfu"}\n'
                       '{"metric": "ok_metric", "value": 2.0}',
               "parsed": None}
        assert gate_mod.extract_metrics(doc) == {"ok_metric": 2.0}

    def test_cli_exit_codes_and_table(self, bench_history):
        cli = [sys.executable, "tools/bench_gate.py",
               "--history-dir", str(bench_history)]
        strict = subprocess.run(cli, cwd=ROOT, capture_output=True, text=True)
        assert strict.returncode == 0
        assert "serving_decode_tokens_per_sec_b8" in strict.stdout
        assert "gate PASSED" in strict.stdout
        # rewinding to the r05 regression round: rc=1 + table
        rewound = subprocess.run(
            cli + ["--exclude", "r06", "--exclude", "r07", "--exclude", "r08"],
            cwd=ROOT, capture_output=True, text=True)
        assert rewound.returncode == 1
        assert "serving_bert_p50_ms_b8" in rewound.stdout
        assert "REGRESSION" in rewound.stdout

    def test_floor_trips_on_a_slow_drift_back(self, gate_mod):
        # -8.5% is inside the 10% relative band, but 37.5 is under the
        # absolute 38.0 flagship floor — the drift back toward the plateau
        # must fail even though no single round slid past tolerance
        rounds = {6: {"resnet50_train_mfu": 41.0},
                  7: {"resnet50_train_mfu": 37.5}}
        results, rc = gate_mod.gate(rounds)
        assert rc == 1
        assert results[0]["verdict"] == "FAIL"
        assert results[0]["floor_breached"] is True

    def test_floor_inactive_before_its_round(self, gate_mod):
        # the same values one round earlier predate the floor: rewound
        # histories must gate exactly as they did then
        rounds = {5: {"resnet50_train_mfu": 41.0},
                  6: {"resnet50_train_mfu": 37.5}}
        results, rc = gate_mod.gate(rounds)
        assert rc == 0
        assert results[0]["verdict"] == "OK"
        assert "floor" not in results[0]

    def test_floor_breach_is_waivable_and_applies_to_baselines(self, gate_mod):
        rounds = {6: {"resnet50_train_mfu": 41.0},
                  7: {"resnet50_train_mfu": 37.5}}
        results, rc = gate_mod.gate(rounds, ["resnet50_train_mfu@r07"])
        assert rc == 0 and results[0]["verdict"] == "WAIVED"
        # a metric FIRST appearing under its floor is not a free pass
        results, rc = gate_mod.gate({7: {"gpt2_medium_mfu_pct": 45.0}})
        assert rc == 1 and results[0]["verdict"] == "FAIL"
        assert results[0]["floor_breached"] is True

    def test_empty_history_is_vacuously_green(self, gate_mod, tmp_path):
        rounds = gate_mod.load_history(tmp_path, [])
        results, rc = gate_mod.gate(rounds)
        assert results == [] and rc == 0


def test_detect_generation_refuses_a_device_the_catalog_does_not_know():
    # the test session's devices are CPUs: no default may price them as v5e
    from kubeflow_tpu.training.flops import detect_generation

    with pytest.raises(ValueError, match="not in the accelerator catalog"):
        detect_generation()


@pytest.mark.parametrize("kind,gen", [
    ("TPU v5 lite", "v5e"), ("TPU v5e", "v5e"), ("TPU v5", "v5p"),
    ("TPU v5p", "v5p"), ("TPU v6 lite", "v6e"), ("TPU v4", "v4")])
def test_detect_generation_maps_device_kinds(monkeypatch, kind, gen):
    from types import SimpleNamespace

    from kubeflow_tpu.training import flops

    monkeypatch.setattr(flops.jax, "devices",
                        lambda: [SimpleNamespace(device_kind=kind)])
    assert flops.detect_generation() == gen
