"""The Sarvam cell at toy size on the CPU: a sound run is ``correct``, the
float8 control and each of the five faults read over the limit; the six new
readers (and the two accepted ones the cell shares with the MiMo cell) on a
hand-made ``obs``; ``sarvam_cost`` against counts by hand; the mix's
documents; the configuration's file against the published keys."""

import json
import time

import jax
import numpy as np
import pytest

from benchmark import flops, harness, moe_cost, sarvam_cost, traffic
from benchmark.metrics import _spans
from benchmark.peaks import peaks_for
from benchmark.runners import sarvam_serve
from benchmark.trace_reduce import Event, Trace

from . import toy_sarvam

CELL = "sarvam-105b.serve.longdoc"


@pytest.fixture(scope="module")
def readings():
    """One warmed toy server: a sound run's share of tokens off the
    reference's best, the bfloat16 reference's and every variant's."""
    cell = toy_sarvam.cell()
    server = sarvam_serve.Server(cell, jax.devices()[:1])
    sizes = server.sizes
    drive = sarvam_serve.SarvamDrive(
        server, traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds),
        cell.seconds)
    drive.run()
    obs = drive.observations()
    picks = sarvam_serve.sample_requests(drive, cell.seed, 8)
    bad = sarvam_serve.malformed(drive, sizes["vocab_size"])
    server.close()
    left = sum(a.nbytes for a in jax.live_arrays())
    served, wrong = sarvam_serve.reference_gaps(drive, picks, cell.seed, sizes,
                                                sarvam_serve.SOUND + sarvam_serve.VARIANTS)
    return {"served": sarvam_serve.off_best_pct(served), "malformed": bad, "obs": obs,
            "checked": len(picks), "left_on_device": left,
            **{name: sarvam_serve.off_best_pct(g) for name, g in wrong.items()}}


LIMIT = toy_sarvam.LIMITS["served_off_best_pct"]


def test_a_sound_run_is_under_the_limit_and_well_formed(readings):
    assert readings["malformed"] == 0 and readings["checked"] == 8
    assert readings["served"] <= LIMIT and readings["reference_bf16"] <= LIMIT
    assert readings["obs"]["moe_assignments_held"] > 0


def test_a_closed_server_leaves_nothing_large_on_the_device(readings):
    """The runner relies on ``close()`` to give the reference the chip's
    memory: the toy's weights alone are 1.8 MB."""
    assert readings["left_on_device"] < 100_000


@pytest.mark.parametrize("who", [v[0] for v in sarvam_serve.VARIANTS])
def test_the_control_and_every_fault_read_over_the_limit(readings, who):
    assert readings[who] > LIMIT, readings


def test_the_run_reports_the_contract_line(capsys):
    cell = toy_sarvam.cell()
    out = sarvam_serve.run(cell, jax.devices()[:1], time.perf_counter())
    assert all(v <= lim for _, v, lim in out.checks), out.checks
    assert [name for name, _, _ in out.checks] == ["malformed_replies", "served_off_best_pct"]
    harness.emit(cell, out, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"serve_ms_per_token_p50", "serve_ms_per_token_p95", "setup_s"} <= set(line["metrics"])
    assert line["served_logit_gap_sd"] >= 0
    assert out.obs["prefill_program_name"] == "prefill_chunk"


def test_limit_readings_give_the_program_and_every_variant_a_row():
    cell = toy_sarvam.cell(seconds=1.0)
    rows = list(sarvam_serve.limit_readings(cell, jax.devices()[:1],
                                            [cell.seed, cell.seed + 7], 1))
    sound = [v[0] for v in sarvam_serve.SOUND]
    assert [r["who"] for r in rows] == sound + [v[0] for v in sarvam_serve.VARIANTS] + [
        "program", "program"]
    for r in rows:
        assert (r["served_off_best_pct"] > LIMIT) == (r["who"] not in sound + ["program"]), r
        assert r["off_first_token"] + r["off_later"] == sum(r["off_by_request"])


def test_the_share_counts_every_token_not_at_the_best():
    gaps = np.array([[0.0, 0.2, np.nan, 0.0], [0.0, 0.0, np.inf, 0.0]])
    assert sarvam_serve.off_best_pct(gaps) == 100 * 3 / 8
    row = sarvam_serve.summary(gaps)
    assert (row["off_first_token"], row["off_later"], row["off_by_request"]) == (0, 3, [2, 1])
    assert row["served_logit_gap_sd"] == float("inf")


# -- the mix and the configuration's file -------------------------------------------

def test_every_prompt_is_the_start_of_one_of_a_few_documents():
    cell = harness.load_cell(CELL, 2**31 + 5, 30.0, False)
    sizes = sarvam_serve.sizes_of(cell.config)
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, 30.0)
    docs = {}
    for a in arrivals:
        assert 4096 <= len(a.prompt) <= 32640
        assert all(1 <= t < sizes["vocab_size"] for t in a.prompt[:64])
        docs.setdefault(tuple(a.prompt[:16]), []).append(len(a.prompt))
    assert len(docs) <= cell.mix["shared_prefix"]["groups"]
    assert max(len(v) for v in docs.values()) >= 3


def test_the_configuration_keeps_every_published_key_but_the_cut():
    config = json.loads((harness.ROOT / "benchmark/configs/sarvam-105b.json").read_text())
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == "sarvam-105b")
    cut = ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert entry["reduced"] == config["reduced"] == cut
    assert config["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                   "vocab_size": 262144, "max_position_embeddings": 131072}
    published = {"hidden_size": 4096, "num_attention_heads": 64, "q_head_dim": 192,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "kv_lora_rank": 512,
                 "v_head_dim": 128, "head_dim": 576, "intermediate_size": 16384,
                 "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
                 "num_shared_experts": 1, "routed_scaling_factor": 2.5,
                 "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
                 "use_qk_norm": True, "tie_word_embeddings": False}
    try:
        rows = [json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")]
        row = next(r for r in rows if r["name"] == "sarvam-105b")
        published = row["config"]
        assert entry["source"] == row["source_url"]
    except OSError:
        pass
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    for group in ("deployment", "cut", "assumed", "runners"):
        assert group in config
    for reading in ("scoring", "norm_topk_prob", "use_qk_norm", "rotary"):
        assert reading in config["assumed"]
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "sarvam-105b", "serve.longdoc", 1)


# -- the readers on a hand-made obs ----------------------------------------------

SIZES = sarvam_serve.sizes_of(toy_sarvam.CONFIG)
NEW = ["mfu.serve.sarvam", "decode_roofline.serve.sarvam", "mla_decode_roofline.serve",
       "mla_prefill_roofline.serve", "attn_mla_prefill_share.serve",
       "attn_mla_decode_share.serve"]


def hand_made_obs():
    """A traced window of 1 s: two decode chunks of 100 ms (4 steps each)
    and one prefill chunk of 50 ms on the device; operations under each
    scope; the engine's regions with their stats."""
    ms = 1e6
    decode = "%latent_decode_attention.{} = f32[4,4,32]{{2,1,0}} custom-call(%a, %b)"
    prefill = "%latent_prefill_attention.{} = bf16[4,16,16]{{2,1,0}} custom-call(%a)"
    ops = [(decode.format(1), "jit(step)/while/body/attn_mla_decode/pallas_call", 10, 20),
           ("fusion.2", "jit(step)/while/body/attn_mla_decode/dot", 30, 5),
           ("fusion.3", "jit(step)/while/body/moe_experts/mul", 40, 10),
           ("fusion.4", "jit(step)/while/body/shared_expert/dot", 50, 6),
           (decode.format(1), "", 210, 30),
           (prefill.format(5), "jit(prefill_chunk)/attn_mla_prefill/pallas_call", 400, 30),
           ("fusion.6", "jit(prefill_chunk)/mla_latent/scatter", 430, 4)]
    scopes = {name: path for name, path, _, _ in ops if path}
    trace = Trace(
        device_ops={"/device:TPU:0": [Event(n, s * ms, d * ms) for n, _, s, d in ops]},
        device_modules={"/device:TPU:0": [Event("jit_step(1)", 0, 100 * ms),
                                          Event("jit_step(1)", 200 * ms, 100 * ms),
                                          Event("jit_prefill_chunk(2)", 400 * ms, 50 * ms)]},
        device_async={}, host_spans=[])
    span = lambda name, at, **stats: _spans.Span(name, at * ms, ms, "t#0", stats)
    spans = [
        span("serving.engine.launch", 395, program="chunk_prefill", start=16, tokens=16,
             view=64),
        span("serving.engine.launch", 396, program="step"),
        span("serving.engine.deliver", 110, kind="chunk", rows=16, tokens=16,
             expert_tokens=40, expert_tokens_max=20, experts_touched=24, latent_pages_read=40),
        span("serving.engine.deliver", 310, kind="chunk", rows=16, tokens=12,
             expert_tokens=60, expert_tokens_max=30, experts_touched=36, latent_pages_read=56),
        span("serving.engine.deliver", 460, kind="first", rows=1, tokens=1,
             expert_tokens=100, expert_tokens_max=25, experts_touched=8, latent_pages_read=0),
    ]
    return {"kind": "serve", "sizes": SIZES, "chips": 1, "device_kind": "TPU v5 lite",
            "window_s": 2.0, "trace": trace, "trace_window": (0.0, 1000 * ms),
            "busy_by_device": {"/device:TPU:0": 0.5},
            "op_scopes": scopes, "serving_spans": spans, "program_name": "step",
            "prefill_program_name": "prefill_chunk", "decode_chunk": 4, "kv_block_t": 4,
            "prompt_len_in_window": [40, 70], "n_out_in_window": [20, 20],
            "moe_assignments_held": 500.0}


def read(name, obs):
    return harness.load_reader(name)(obs)


def test_every_new_reader_reads_the_hand_made_obs():
    obs = hand_made_obs()
    peak, bw = 197e12, 819e9
    assert read("attn_mla_decode_share.serve", obs) == pytest.approx(100 * 0.055 / 0.5)
    assert read("attn_mla_prefill_share.serve", obs) == pytest.approx(100 * 0.030 / 0.5)
    work = sum(sarvam_cost.prefill_flops(SIZES, p) + sarvam_cost.decode_flops(SIZES, p, 20)
               for p in (40, 70)) + sarvam_cost.expert_flops(SIZES, 500)
    assert read("mfu.serve.sarvam", obs) == pytest.approx(100 * work / (2.0 * peak))
    # a step: 12 experts touched and 12 pages a layer (two chunks of 4 steps)
    step_s = 0.1 / 4
    need = sarvam_cost.decode_step_bytes(SIZES, 60 / 8, 96 / 8, 4)
    assert read("decode_roofline.serve.sarvam", obs) == pytest.approx(100 * need / bw / step_s)
    # 2 executions x 4 steps x 3 layers, 12 pages each
    cost = sarvam_cost.mla_decode_cost(SIZES, 2 * 4 * 3 * 12, 4)
    least = max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("mla_decode_roofline.serve", obs) == pytest.approx(100 * least / 0.050)
    cost = sarvam_cost.mla_prefill_cost(SIZES, 16, 16)
    least = 3 * max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("mla_prefill_roofline.serve", obs) == pytest.approx(100 * least / 0.030)
    # the accepted readers the cell shares with the MiMo cell: the grouped
    # products under moe_experts in the two step executions, 100 held
    # assignments and 60 experts touched over the two chunks delivered
    assert read("moe_share.serve", obs) == pytest.approx(100 * 0.010 / 0.25)
    least = flops.roofline_seconds(moe_cost.grouped_matmul_cost(SIZES, 100, 60),
                                   peaks_for("TPU v5 lite"))
    assert read("moe_experts_roofline.serve", obs) == pytest.approx(100 * least / 0.010)
    assert read("expert_load_skew.serve", obs) == pytest.approx(75 * 8 / 200)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Another configuration's run, and a run of this one with no trace:
    None, never 0 and never an exception."""
    gpt = {"kind": "serve", "sizes": {"n_layer": 2}, "prompt_len_in_window": [3],
           "n_out_in_window": [2], "window_s": 1.0, "chips": 1, "device_kind": "TPU v5 lite",
           "program_name": "step", "decode_chunk": 16, "kv_block_t": 16}
    assert read(name, gpt) is None
    assert read(name, {"kind": "train"}) is None
    bare = {k: v for k, v in hand_made_obs().items()
            if k not in ("trace", "trace_window", "serving_spans", "op_scopes",
                         "moe_assignments_held", "busy_by_device")}
    assert read(name, bare) is None


# -- sarvam_cost against counts by hand ---------------------------------------------------

S = {"d_model": 8, "n_heads": 2, "qk_nope_dim": 3, "qk_rope_dim": 2, "kv_lora_rank": 4,
     "v_dim": 2, "moe_layers": [0, 1], "d_ff_dense": 16, "d_ff_expert": 5, "d_ff_shared": 6,
     "n_experts": 10, "held_experts": 2, "experts_per_token": 2, "vocab_size": 7}


def test_sarvam_cost_against_counts_by_hand():
    attn = 8 * 2 * 5 + 8 * 6 + 2 * 4 * (3 + 2) + 2 * 2 * 8      # q, dkv, uk + uv, o
    assert sarvam_cost.attention_params(S) == attn == 200
    assert sarvam_cost.row_width(S) == 128
    dense = attn + 3 * 8 * 16 + attn + 8 * 10 + 3 * 8 * 6
    assert sarvam_cost.dense_params(S) == dense
    # a key costs 2 * heads * (qk + v) = 28 a layer, two layers
    span = 28 * 2 * sum(p + 1 for p in range(3, 7))
    assert sarvam_cost.attention_flops_span(S, 3, 4) == span
    assert sarvam_cost.prefill_flops(S, 6) == 2 * dense * 6 + 28 * 2 * 21 + 2 * 8 * 7
    assert sarvam_cost.decode_flops(S, 6, 4) == 3 * 2 * (dense + 56) + 28 * 2 * (7 + 8 + 9)
    assert sarvam_cost.page_bytes(S, 4) == 4 * 128 * 2
    router = 8 * 10
    assert sarvam_cost.decode_step_bytes(S, 3, 5, 4) == (
        2 * (dense - router + 56) + 4 * router + 2 * 3 * 8 * 5 * 3 + 2 * 1024 * 5)
    cost = sarvam_cost.mla_decode_cost(S, 5, 4)
    assert cost == {"flops": 2.0 * 2 * (2 * 4 + 2) * 4 * 5, "bytes": 1024.0 * 5}
    cost = sarvam_cost.mla_prefill_cost(S, 8, 4)
    seen = 4 * 8 + 10
    assert cost["flops"] == 2 * 2 * 4 * 5 * 12 + 2 * 2 * 7 * seen
    assert cost["bytes"] == 12 * 128 * 2 + 2 * 4 * 5 * 2
