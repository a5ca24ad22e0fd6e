"""The Nemotron cell at toy size on the CPU: a sound run is ``correct``, the
float8 control and each of the five faults read over the limit, and the
control and both carry faults over the carry probe's; the six new
readers (and the accepted ones the cell shares with the MiMo cell) on a
hand-made ``obs``; ``nemotron_cost`` against counts by hand; the mix; the
configuration's file against the published keys."""

import json
import time

import jax
import numpy as np
import pytest

from benchmark import flops, harness, nemotron_cost, traffic
from benchmark.metrics import _spans
from benchmark.peaks import peaks_for
from benchmark.runners import nemotron_serve
from benchmark.trace_reduce import Event, Trace

from . import toy_nemotron

CELL = "nemotron-3-super-120b-a12b.serve.agentic"
LIMIT = toy_nemotron.LIMITS["served_off_best_pct"]


@pytest.fixture(scope="module")
def readings():
    """One warmed toy server: a sound run's share of tokens off the
    reference's best, the bfloat16 reference's and every variant's."""
    cell = toy_nemotron.cell()
    server = nemotron_serve.Server(cell, jax.devices()[:1])
    sizes = server.sizes
    drive = nemotron_serve.NemotronDrive(
        server, traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds),
        cell.seconds)
    drive.run()
    obs = drive.observations()
    picks = nemotron_serve.sample_requests(drive, cell.seed, 8)
    bad = nemotron_serve.malformed(drive, sizes["vocab_size"])
    probe = nemotron_serve.carry_probe(server, cell.seed, **cell.params["carry_probe"])
    server.close()
    left = sum(a.nbytes for a in jax.live_arrays())
    variants = nemotron_serve.SOUND + nemotron_serve.VARIANTS
    served, wrong = nemotron_serve.reference_gaps(
        *nemotron_serve.checked(drive, picks), server.prefill_chunk, cell.seed, sizes, variants)
    carried, carry_wrong = nemotron_serve.reference_gaps(
        *probe, server.prefill_chunk, cell.seed, sizes, [v[:3] + (0,) for v in variants])
    return {"served": nemotron_serve.off_best_pct(served), "malformed": bad, "obs": obs,
            "checked": len(picks), "left_on_device": left, "probe": probe,
            "carry": nemotron_serve.off_best_pct(carried),
            **{name: nemotron_serve.off_best_pct(g) for name, g in wrong.items()},
            **{f"carry_{name}": nemotron_serve.off_best_pct(g)
               for name, g in carry_wrong.items()}}


def test_a_sound_run_is_under_the_limit_and_well_formed(readings):
    assert readings["malformed"] == 0 and readings["checked"] == 8
    assert readings["served"] <= LIMIT and readings["reference_bf16"] <= LIMIT
    assert readings["obs"]["moe_assignments_held"] > 0 and readings["obs"]["ssm_state_rows"] > 0


def test_a_closed_server_leaves_nothing_large_on_the_device(readings):
    assert readings["left_on_device"] < 100_000


@pytest.mark.parametrize("who", [v[0] for v in nemotron_serve.VARIANTS])
def test_the_control_and_every_fault_read_over_the_limit(readings, who):
    assert readings[who] > LIMIT, readings


def test_the_carry_probe_ends_its_prompts_just_past_a_chunk_boundary(readings):
    seqs, prompts = readings["probe"]
    chunk = toy_nemotron.CONFIG["runners"]["nemotron_serve"]["prefill_chunk"]
    assert len(seqs) == toy_nemotron.PROBE["requests"]
    assert sorted(set(prompts)) == [chunk + 1, chunk + 2, chunk + 3]
    vocab = toy_nemotron.CONFIG["vocab_size"]
    assert all(len(q) == p + toy_nemotron.PROBE["new_tokens"]
               and all(0 <= t < vocab for t in q) for q, p in zip(seqs, prompts))


@pytest.mark.parametrize("who", ["control_fp8", "fault_state_reset", "fault_conv_not_carried"])
def test_the_carry_probe_sees_the_carry_faults(readings, who):
    """A sound program reads under the probe's limit; the control and
    both faults of the carry read over it."""
    limit = toy_nemotron.LIMITS["carry_off_best_pct"]
    assert readings["carry"] <= limit and readings["carry_reference_bf16"] <= limit
    assert readings[f"carry_{who}"] > limit, readings


def test_the_run_reports_the_contract_line(capsys):
    cell = toy_nemotron.cell()
    out = nemotron_serve.run(cell, jax.devices()[:1], time.perf_counter())
    assert all(v <= lim for _, v, lim in out.checks), out.checks
    assert [name for name, _, _ in out.checks] == ["malformed_replies", "served_off_best_pct",
                                                   "carry_off_best_pct"]
    harness.emit(cell, out, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"serve_ms_per_token_p50", "serve_ms_per_token_p95", "setup_s"} <= set(line["metrics"])
    assert line["served_logit_gap_sd"] >= 0
    assert out.obs["prefill_program_name"] == "prefill_chunk"


# -- the mix and the configuration's file -------------------------------------------

def test_every_prompt_and_answer_fits_a_slot():
    cell = harness.load_cell(CELL, 2**31 + 5, 30.0, False)
    sizes = nemotron_serve.sizes_of(cell.config)
    new = cell.deploy["max_new_tokens"]
    arrivals = traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, 30.0)
    lengths = [len(a.prompt) for a in arrivals]
    assert 1024 <= min(lengths) and max(lengths) + new <= sizes["max_seq"]
    assert np.median(lengths) > cell.deploy["prefill_chunk"]    # most carry a state over
    assert all(1 <= t < sizes["vocab_size"] for a in arrivals for t in a.prompt[:64])


def test_the_configuration_keeps_every_published_key_but_the_cut():
    config = json.loads((harness.ROOT / "benchmark/configs/nemotron-3-super-120b-a12b.json")
                        .read_text())
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == "nemotron-3-super-120b-a12b")
    cut = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers"]
    assert entry["reduced"] == config["reduced"] == cut
    pattern = config["published"]["hybrid_override_pattern"]
    assert pattern[26:37] == config["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    published = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
                 "ssm_state_size": 128, "n_groups": 8, "expand": 2, "conv_kernel": 4,
                 "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "num_experts_per_tok": 22, "moe_latent_size": 1024,
                 "moe_intermediate_size": 2688, "moe_shared_expert_intermediate_size": 5376,
                 "routed_scaling_factor": 5, "norm_eps": 1e-5, "mlp_hidden_act": "relu2",
                 "tie_word_embeddings": False}
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    for group in ("deployment", "cut", "assumed", "runners"):
        assert group in config
    for reading in ("attention_positions", "router_input", "latent", "state_precision"):
        assert reading in config["assumed"]
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "nemotron-3-super-120b-a12b", "serve.agentic", 1)


# -- the readers on a hand-made obs ----------------------------------------------

SIZES = nemotron_serve.sizes_of(toy_nemotron.CONFIG)
NEW = ["ssm_share.serve", "ssm_state_roofline.serve", "ssm_prefill_roofline.serve",
       "mfu.serve.nemotron", "decode_roofline.serve.nemotron",
       "moe_experts_roofline.serve.nemotron"]


def hand_made_obs():
    """A traced window of 1 s: two decode chunks of 100 ms (4 steps each)
    and one prefill chunk of 50 ms on the device; operations under each
    scope; the engine's regions with their stats."""
    ms = 1e6
    update = "%ssm_decode_update.{} = f32[4,2,16,8]{{3,2,1,0}} custom-call(%a, %b)"
    ops = [(update.format(1), "jit(step)/while/body/mamba/ssm_update/pallas_call", 10, 20),
           ("fusion.2", "jit(step)/while/body/mamba/dot", 30, 5),
           ("fusion.3", "jit(step)/while/body/moe_experts/mul", 40, 10),
           ("fusion.4", "jit(step)/while/body/shared_expert/dot", 50, 6),
           (update.format(1), "", 210, 30),
           ("fusion.5", "jit(prefill_chunk)/mamba/ssm_scan/dot", 400, 30),
           ("fusion.6", "jit(prefill_chunk)/attn_gqa/kv_write/scatter", 430, 4)]
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
             expert_tokens=40, expert_tokens_max=20, experts_touched=24, ssm_rows=16,
             kv_pages_read=40),
        span("serving.engine.deliver", 310, kind="chunk", rows=16, tokens=12,
             expert_tokens=60, expert_tokens_max=30, experts_touched=36, ssm_rows=32,
             kv_pages_read=56),
        span("serving.engine.deliver", 460, kind="first", rows=1, tokens=1,
             expert_tokens=100, expert_tokens_max=25, experts_touched=8, ssm_rows=2,
             kv_pages_read=0),
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
    # under `mamba`: the kernel 20 ms + 30 ms and its neighbour 5 ms in the
    # step, the scan 30 ms in the chunk, over the three executions' 250 ms
    assert read("ssm_share.serve", obs) == pytest.approx(100 * 0.085 / 0.25)
    # 6 (row, layer) states a step (48 over two chunks of 4 steps), 2 runs
    need = nemotron_cost.ssm_state_bytes(SIZES, 6 * 2 * 4)
    assert read("ssm_state_roofline.serve", obs) == pytest.approx(100 * need / bw / 0.050)
    cost = nemotron_cost.ssm_scan_cost(SIZES, 16)
    least = 2 * max(cost["flops"] / peak, cost["bytes"] / bw)     # two Mamba layers
    assert read("ssm_prefill_roofline.serve", obs) == pytest.approx(100 * least / 0.030)
    work = sum(nemotron_cost.prefill_flops(SIZES, p) + nemotron_cost.decode_flops(SIZES, p, 20)
               for p in (40, 70)) + nemotron_cost.expert_flops(SIZES, 500)
    assert read("mfu.serve.nemotron", obs) == pytest.approx(100 * work / (2.0 * peak))
    step_s = 0.1 / 4
    need = nemotron_cost.decode_step_bytes(SIZES, 60 / 8, 48 / 8, 96 / 8, 4)
    assert read("decode_roofline.serve.nemotron", obs) == pytest.approx(100 * need / bw / step_s)
    least = flops.roofline_seconds(nemotron_cost.grouped_cost(SIZES, 100, 60),
                                   peaks_for("TPU v5 lite"))
    assert read("moe_experts_roofline.serve.nemotron", obs) == pytest.approx(100 * least / 0.010)
    # the accepted readers the cell shares with the MiMo cell
    assert read("moe_share.serve", obs) == pytest.approx(100 * 0.010 / 0.25)
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


# -- nemotron_cost against counts by hand ---------------------------------------------

S = {"d_model": 8, "pattern": "ME*E", "mamba_heads": 2, "mamba_head_dim": 3, "ssm_state": 4,
     "n_groups": 1, "conv_kernel": 4, "ssm_chunk": 2, "n_heads": 2, "kv_heads": 1,
     "head_dim": 4, "n_experts": 10, "held_experts": 2, "experts_per_token": 2,
     "moe_latent": 3, "d_ff_expert": 5, "d_ff_shared": 6, "vocab_size": 7}


def test_nemotron_cost_against_counts_by_hand():
    mamba = 8 * (2 * 6 + 2 * 4 + 2) + 6 * 8                     # W_in, W_out
    assert nemotron_cost.mamba_params(S) == mamba == 224
    attn = 2 * 8 * 2 * 4 + 2 * 8 * 1 * 4                         # q, o; k, v
    assert nemotron_cost.attention_params(S) == attn == 192
    moe = 8 * 10 + 2 * 8 * 3 + 2 * 8 * 6                          # router, latent, shared
    assert nemotron_cost.dense_params(S) == mamba + attn + 2 * moe
    assert nemotron_cost.expert_params(S) == 30
    state = 4 * 2 * 3 * 4                                         # per position, one layer
    assert nemotron_cost.state_flops(S, 5) == state * 5
    # a key costs 4 * heads * head_dim = 32 in the one attention layer
    assert nemotron_cost.attention_flops_span(S, 3, 4) == 32 * sum(p + 1 for p in range(3, 7))
    dense = nemotron_cost.dense_params(S)
    assert nemotron_cost.prefill_flops(S, 6) == 2 * dense * 6 + state * 6 + 32 * 21 + 2 * 56
    assert nemotron_cost.decode_flops(S, 6, 4) == 3 * 2 * (dense + 56) + state * 3 + 32 * 24
    assert nemotron_cost.state_bytes(S) == 2 * 3 * 4 * 4
    assert nemotron_cost.window_bytes(S) == 3 * (6 + 8) * 4
    assert nemotron_cost.ssm_state_bytes(S, 3) == 3 * (2 * 96 + (12 + 2 + 8) * 4)
    cost = nemotron_cost.ssm_scan_cost(S, 4)
    assert cost["flops"] == 4 * (2 * 2 * (4 + 6) + 4 * 24)
    assert cost["bytes"] == 4 * (12 + 2 + 8) * 4 + 2 * 96
    cost = nemotron_cost.grouped_cost(S, 3, 2)
    assert cost == {"flops": 2 * 30 * 3, "bytes": 2 * 30 * 2 + 3 * (3 * 2 + 2 * 5 * 2 + 3 * 4)}
    router = 2 * 8 * 10
    assert nemotron_cost.decode_step_bytes(S, 3, 2, 5, 4) == (
        2 * (dense - router + 56) + 4 * router + 2 * 30 * 3 + 2 * 2 * (96 + 168)
        + 2 * 4 * 1 * 4 * 2 * 5)
