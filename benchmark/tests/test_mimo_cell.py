"""The MiMo cell at toy size on the CPU: a sound run is ``correct``, the
float8 control and each of the three faults read over the limit; the eight
new readers on a hand-made ``obs``; ``moe_cost`` against counts by hand."""

import json
import time

import jax
import pytest

from benchmark import harness, moe_cost, traffic
from benchmark.metrics import _spans
from benchmark.runners import mimo_serve
from benchmark.trace_reduce import Event, Trace

from . import toy_mimo


@pytest.fixture(scope="module")
def readings():
    """One warmed toy server: a sound run's gaps and every variant's."""
    cell = toy_mimo.cell()
    server = mimo_serve.Server(cell, jax.devices()[:1])
    sizes = server.sizes
    drive = mimo_serve.MimoDrive(
        server, traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds),
        cell.seconds)
    drive.run()
    obs = drive.observations()
    picks = mimo_serve.sample_requests(drive, cell.seed, 12)
    bad = mimo_serve.malformed(drive, sizes["vocab_size"])
    server.close()
    served, wrong = mimo_serve.reference_gaps(drive, picks, cell.seed, sizes,
                                              mimo_serve.VARIANTS)
    return {"served": float(served.max()), "malformed": bad, "obs": obs,
            **{name: float(g.max()) for name, g in wrong.items()}}


def test_a_sound_run_is_under_the_limit_and_well_formed(readings):
    assert readings["malformed"] == 0
    assert readings["served"] <= toy_mimo.LIMITS["served_logit_gap_sd"]


@pytest.mark.parametrize("who", ["control_fp8", "fault_no_window", "fault_no_sink",
                                 "fault_top7"])
def test_the_control_and_every_fault_read_over_the_limit(readings, who):
    assert readings[who] > toy_mimo.LIMITS["served_logit_gap_sd"], readings


def test_the_run_reports_the_contract_line(capsys):
    cell = toy_mimo.cell()
    out = mimo_serve.run(cell, jax.devices()[:1], time.perf_counter())
    assert all(v <= lim for _, v, lim in out.checks), out.checks
    harness.emit(cell, out, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"serve_ms_per_token_p50", "serve_ms_per_token_p95", "setup_s"} <= set(line["metrics"])
    assert out.obs["moe_assignments_held"] > 0
    assert out.obs["prefill_program_name"] == "prefill_chunk"


# -- the readers on a hand-made obs ----------------------------------------------

SIZES = mimo_serve.sizes_of(toy_mimo.CONFIG)
NEW = ["mfu.serve.mimo", "decode_roofline.serve.mimo", "moe_experts_roofline.serve",
       "moe_share.serve", "attn_full_share.serve", "attn_window_share.serve",
       "window_kv_block_share.serve", "expert_load_skew.serve"]


def hand_made_obs():
    """A traced window of 1 s: two decode chunks of 100 ms and one prefill
    chunk of 50 ms on the device; operations under each scope; the
    engine's regions with their stats."""
    ms = 1e6
    ops = [("fusion.1", "jit(step)/while/body/attn_full/dot", 10, 30),
           ("fusion.2", "jit(step)/while/body/attn_window/dot", 40, 10),
           ("fusion.6", "jit(step)/while/body/moe_experts/mul", 50, 4),
           # the kernel XLA makes of ragged_dot keeps no scope: found by name
           ("%ragged-dot-none.7 = f32[64,64]{1,0} custom-call(%a, %b)", "", 54, 16),
           ("fusion.3", "jit(step)/while/body/moe_router/dot", 70, 5),
           ("fusion.1", "", 210, 30),
           ("%ragged-dot-none.7 = f32[64,64]{1,0} custom-call(%a, %b)", "", 250, 20),
           ("fusion.4", "jit(prefill_chunk)/attn_full/while/body/dot", 400, 25),
           ("fusion.5", "jit(prefill_chunk)/moe_combine/gather", 430, 5)]
    scopes = {name: path for name, path, _, _ in ops if path}
    trace = Trace(
        device_ops={"/device:TPU:0": [Event(n, s * ms, d * ms) for n, _, s, d in ops]},
        device_modules={"/device:TPU:0": [Event("jit_step(1)", 0, 100 * ms),
                                          Event("jit_step(1)", 200 * ms, 100 * ms),
                                          Event("jit_prefill_chunk(2)", 400 * ms, 50 * ms)]},
        device_async={}, host_spans=[])
    span = lambda name, at, **stats: _spans.Span(name, at * ms, ms, "t#0", stats)
    spans = [
        span("serving.engine.dispatch", 1, window_blocks=12, window_blocks_unreleased=40,
             full_blocks=30, view_blocks=8, max_blocks=32),
        span("serving.engine.dispatch", 201, window_blocks=8, window_blocks_unreleased=60,
             full_blocks=31, view_blocks=16, max_blocks=32),
        span("serving.engine.deliver", 110, kind="chunk", rows=16, tokens=16,
             expert_tokens=40, expert_tokens_max=20, experts_touched=24),
        span("serving.engine.deliver", 310, kind="chunk", rows=16, tokens=12,
             expert_tokens=60, expert_tokens_max=30, experts_touched=36),
        span("serving.engine.deliver", 460, kind="first", rows=1, tokens=1,
             expert_tokens=100, expert_tokens_max=25, experts_touched=8),
    ]
    return {"kind": "serve", "sizes": SIZES, "chips": 1, "device_kind": "TPU v5 lite",
            "window_s": 2.0, "trace": trace, "trace_window": (0.0, 1000 * ms),
            "op_scopes": scopes, "serving_spans": spans, "program_name": "step",
            "prefill_program_name": "prefill_chunk", "decode_chunk": 4, "kv_block_t": 4,
            "prompt_len_in_window": [10, 30], "n_out_in_window": [8, 8],
            "moe_assignments_held": 500.0,
            "kv_blocks_used_full": [30.0, 32.0], "kv_blocks_used_window": [10.0, 14.0]}


def read(name, obs):
    return harness.load_reader(name)(obs)


def test_every_new_reader_reads_the_hand_made_obs():
    obs = hand_made_obs()
    programs_s = 0.25
    assert read("attn_full_share.serve", obs) == pytest.approx(100 * 0.085 / programs_s)
    assert read("attn_window_share.serve", obs) == pytest.approx(100 * 0.010 / programs_s)
    assert read("moe_share.serve", obs) == pytest.approx(100 * 0.050 / programs_s)
    assert read("window_kv_block_share.serve", obs) == pytest.approx(20.0)
    # the accepted reader of the dispatch both families share: the full kind's view
    assert read("decode_view_block_share.serve", obs) == pytest.approx(100 * 24 / 64)
    # (20 + 30 + 25) * 8 held / (40 + 60 + 100)
    assert read("expert_load_skew.serve", obs) == pytest.approx(75 * 8 / 200)
    peak, bw = 197e12, 819e9
    work = sum(moe_cost.prefill_flops(SIZES, p) + moe_cost.decode_flops(SIZES, p, 8)
               for p in (10, 30)) + moe_cost.expert_flops(SIZES, 500)
    assert read("mfu.serve.mimo", obs) == pytest.approx(100 * work / (2.0 * peak))
    # two executions, two chunks delivered: the chunks' own counters
    cost = moe_cost.grouped_matmul_cost(SIZES, 100, 60)
    least = max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("moe_experts_roofline.serve", obs) == pytest.approx(100 * least / 0.040)
    step_s = 0.1 / 4
    need = moe_cost.decode_step_bytes(SIZES, 4 * 31.0, 4 * 12.0, 60 / (2 * 4))
    assert read("decode_roofline.serve.mimo", obs) == pytest.approx(100 * need / bw / step_s)


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
                         "moe_assignments_held")}
    assert read(name, bare) is None


# -- moe_cost against counts by hand ---------------------------------------------------

S = {"d_model": 8, "n_heads": 4, "qk_dim": 6, "v_dim": 2, "kv_heads_full": 1,
     "kv_heads_window": 2, "window": 3, "layer_kinds": [0, 1], "moe_layers": [0, 1],
     "d_ff_dense": 16, "d_ff_expert": 5, "n_experts": 10, "held_experts": 2,
     "experts_per_token": 2, "vocab_size": 7}


def test_moe_cost_against_counts_by_hand():
    full = 8 * 4 * 6 + 8 * 1 * 6 + 8 * 1 * 2 + 4 * 2 * 8            # q, k, v, o
    window = 8 * 4 * 6 + 8 * 2 * 6 + 8 * 2 * 2 + 4 * 2 * 8
    assert moe_cost.attention_params(S, 0) == full == 320
    assert moe_cost.attention_params(S, 1) == window == 384
    assert moe_cost.expert_params(S) == 3 * 8 * 5
    assert moe_cost.dense_params(S) == full + 3 * 8 * 16 + window + 8 * 10
    # a query at position 4: the full layer sees 5 keys, the window layer 3;
    # a key costs 2 * heads * (qk + v) = 64
    assert moe_cost.attention_flops(S, 4) == 64 * (5 + 3)
    assert moe_cost.attention_flops(S, 0) == 64 * (1 + 1)
    span = sum(moe_cost.attention_flops(S, p) for p in range(2, 9))
    assert moe_cost.attention_flops_span(S, 2, 7) == span
    dense = moe_cost.dense_params(S)
    assert moe_cost.prefill_flops(S, 6) == 2 * dense * 6 + sum(
        moe_cost.attention_flops(S, p) for p in range(6)) + 2 * 8 * 7
    assert moe_cost.decode_flops(S, 6, 4) == 3 * 2 * (dense + 8 * 7) + sum(
        moe_cost.attention_flops(S, p) for p in range(6, 9))
    assert moe_cost.expert_flops(S, 9) == 9 * 2 * 120
    cost = moe_cost.grouped_matmul_cost(S, 9, 2)
    assert cost["flops"] == 9 * 240
    assert cost["bytes"] == 2 * 120 * 2 + 9 * (2 * 8 * 2 + 2 * 5 * 4 + 5 * 2 + 8 * 4)
    # one full layer of 1 KV head, one window layer of 2, 8 dims each at 2 bytes
    assert moe_cost.kv_bytes_per_token(S, 0) == 16 and moe_cost.kv_bytes_per_token(S, 1) == 32
    assert moe_cost.decode_step_bytes(S, 100, 10, 3) == (
        2 * (dense + 56) + 2 * 120 * 3 + 16 * 100 + 32 * 10)
