"""The SDAR cell at toy size on the CPU: a sound run is ``correct``, the
float8 control and each of the five faults read over one of the two limits;
the seven new readers (and the two accepted ones the cell shares with the
MiMo cell) on a hand-made ``obs``; ``sdar_cost`` against counts by hand; the
two-stream reading the check uses against the reference's own naive
forward; the mix never draws the mask id; the configuration's file against
the published keys."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, sdar_cost, weights_sdar
from benchmark.metrics import _spans
from benchmark.reference import sdar as ref
from benchmark.runners import sdar_serve
from benchmark.trace_reduce import Event, Trace

from . import toy_sdar

CELL = "sdar-30b-a3b.serve.blockchat"


@pytest.fixture(scope="module")
def readings():
    """One warmed toy server: a sound run's gaps and every variant's."""
    cell = toy_sdar.cell()
    server = sdar_serve.Server(cell, jax.devices()[:1])
    sizes = server.sizes
    drive = sdar_serve.SdarDrive(
        server, sdar_serve.arrivals_of(cell.mix, sizes, cell.seed, cell.seconds), cell.seconds)
    drive.run()
    obs = drive.observations()
    picks = sdar_serve.sample_requests(drive, cell.seed, 12)
    bad = sdar_serve.malformed(drive, sizes)
    server.close()
    served, wrong = sdar_serve.reference_gaps(drive, picks, cell.seed, sizes,
                                              sdar_serve.VARIANTS)
    worst = lambda gaps: {k: sdar_serve.worst(v) for k, v in gaps.items()}
    return {"served": worst(served), "malformed": bad, "obs": obs, "drive": drive,
            "passes": len(served["choice"]), **{name: worst(g) for name, g in wrong.items()}}


def over(reading):
    return (reading["token"] > toy_sdar.LIMITS["served_logit_gap_sd"]
            or reading["choice"] > toy_sdar.LIMITS["reveal_choice_gap_sd"])


def test_a_sound_run_is_under_both_limits_and_well_formed(readings):
    assert readings["malformed"] == 0 and not over(readings["served"]), readings["served"]
    # the check reaches every kind of pass: more than a hundred of them
    assert readings["passes"] > 100
    drive = readings["drive"]
    assert all(m is None or len(m) == 22 for m in drive.marks)


@pytest.mark.parametrize("who", [name for name, _, _ in sdar_serve.VARIANTS])
def test_the_control_and_every_fault_read_over_a_limit(readings, who):
    assert over(readings[who]), readings


def test_left_to_right_shows_in_the_choice_alone(readings):
    """Its tokens are the sound model's; only the second number sees it."""
    wrong = readings["fault_left_to_right"]
    assert wrong["token"] == readings["served"]["token"]
    assert wrong["choice"] > toy_sdar.LIMITS["reveal_choice_gap_sd"]


def test_the_run_reports_the_contract_line(capsys):
    cell = toy_sdar.cell()
    out = sdar_serve.run(cell, jax.devices()[:1], time.perf_counter())
    assert all(v <= lim for _, v, lim in out.checks), out.checks
    assert [name for name, _, _ in out.checks] == [
        "malformed_replies", "served_logit_gap_sd", "reveal_choice_gap_sd"]
    harness.emit(cell, out, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"serve_ms_per_token_p50", "serve_ms_per_token_p95", "setup_s"} <= set(line["metrics"])
    assert out.obs["prefill_program_name"] == "prefill_chunk"


def test_limit_readings_give_the_program_and_every_variant_a_row():
    cell = toy_sdar.cell(seconds=1.0)
    rows = list(sdar_serve.limit_readings(cell, jax.devices()[:1], [cell.seed, cell.seed + 7], 1))
    who = [r["who"] for r in rows]
    assert who == [name for name, _, _ in sdar_serve.VARIANTS] + ["program", "program"]
    for r in rows:
        wrong = over({"token": r["served_logit_gap_sd"], "choice": r["reveal_choice_gap_sd"]})
        assert wrong == (r["who"] != "program"), r
    assert all(r["malformed_replies"] == 0 for r in rows if r["who"] == "program")


# -- the mix and the configuration's file -------------------------------------------

def test_the_mix_never_draws_the_mask_id_and_reaches_every_other_id():
    sizes = {"vocab_size": 12, "mask_id": 7}
    mix = {"kind": "open_loop", "rate_rps": 50.0, "lead_in_s": 0.0,
           "prompt_len": {"kind": "fixed", "value": 40}}
    ids = {t for a in sdar_serve.arrivals_of(mix, sizes, 2**31 + 1, 1.0) for t in a.prompt}
    assert ids == set(range(1, 12)) - {7}
    cell = harness.load_cell(CELL, 2**31 + 5, 2.0, False)
    full = sdar_serve.sizes_of(cell.config)
    for a in sdar_serve.arrivals_of(cell.mix, full, cell.seed, 2.0):
        assert full["mask_id"] not in a.prompt and 32 <= len(a.prompt) <= 2048
        assert all(1 <= t < full["vocab_size"] for t in a.prompt)


def test_the_configuration_keeps_every_published_key_but_the_depth():
    """Against the catalog's row beside the model-configs guide, where it
    can be read here; else against the widths ISSUE 35 names."""
    config = json.loads((harness.ROOT / "benchmark/configs/sdar-30b-a3b.json").read_text())
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48} and config["num_hidden_layers"] == 6
    published = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
                 "head_dim": 128, "num_experts": 128, "moe_intermediate_size": 768,
                 "num_experts_per_tok": 8, "vocab_size": 151936, "rope_theta": 1000000,
                 "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
                 "tie_word_embeddings": False, "max_position_embeddings": 32768}
    try:
        rows = [json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")]
        published = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")["config"]
        assert entry["source"] == next(r for r in rows
                                       if r["name"] == "SDAR-30B-A3B-Chat")["source_url"]
    except OSError:
        pass
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    for group in ("deployment", "cut", "assumed", "generation", "runners"):
        assert group in config
    workload = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "sdar-30b-a3b", "serve.blockchat", 1)


# -- the readers on a hand-made obs ----------------------------------------------

SIZES = sdar_serve.sizes_of(toy_sdar.CONFIG)
NEW = ["mfu.serve.sdar", "decode_roofline.serve.sdar", "block_attention_roofline.serve",
       "moe_experts_roofline.serve.sdar", "commit_forward_share.serve", "unmask_share.serve",
       "tokens_per_forward.serve"]


def hand_made_obs():
    """A traced window of 1 s: two decode dispatches of 100 ms (4 passes
    each) and one prefill chunk of 50 ms on the device; operations under
    each scope; the engine's regions with their stats."""
    ms = 1e6
    kernel = "%paged_decode_attention.{} = f32[4,32,16]{{2,1,0}} custom-call(%a, %b)"
    ragged = "%ragged-dot-none.{} = f32[64,64]{{1,0}} fusion(%a, %b)"
    ops = [(kernel.format(1), "jit(step)/while/body/attn_block/pallas_call", 10, 8),
           (ragged.format(2), "", 20, 30),
           ("fusion.3", "jit(step)/while/body/moe_experts/mul", 50, 5),
           ("fusion.4", "jit(step)/while/body/moe_router/dot", 55, 2),
           ("fusion.5", "jit(step)/while/body/lm_head/dot", 60, 12),
           ("fusion.6", "jit(step)/while/body/unmask/reduce", 72, 8),
           ("fusion.7", "jit(step)/while/body/moe_combine/gather", 80, 3),
           (kernel.format(1), "", 210, 12),
           ("fusion.5", "", 260, 10),
           ("fusion.8", "jit(prefill_chunk)/attn_block/chunk_attention", 400, 20),
           ("fusion.9", "jit(prefill_chunk)/moe_dispatch/sort", 430, 5)]
    scopes = {name: path for name, path, _, _ in ops if path}
    trace = Trace(
        device_ops={"/device:TPU:0": [Event(n, s * ms, d * ms) for n, _, s, d in ops]},
        device_modules={"/device:TPU:0": [Event("jit_step(1)", 0, 100 * ms),
                                          Event("jit_step(1)", 200 * ms, 100 * ms),
                                          Event("jit_prefill_chunk(2)", 400 * ms, 50 * ms)]},
        device_async={}, host_spans=[])
    span = lambda name, at, **stats: _spans.Span(name, at * ms, ms, "t#0", stats)
    spans = [
        span("serving.engine.dispatch", 1, rows=16, live=3, view_blocks=8, max_blocks=32),
        span("serving.engine.dispatch", 201, rows=16, live=4, view_blocks=16, max_blocks=32),
        span("serving.engine.deliver", 110, kind="chunk", rows=32, tokens=8, retired=0,
             expert_tokens=400, expert_tokens_max=60, experts_touched=100, forwards=12,
             commit_forwards=3, blocks_committed=3, revealed=9, blocks_read=90),
        span("serving.engine.deliver", 310, kind="chunk", rows=32, tokens=12, retired=1,
             expert_tokens=500, expert_tokens_max=70, experts_touched=120, forwards=16,
             commit_forwards=2, blocks_committed=2, revealed=14, blocks_read=150),
        span("serving.engine.deliver", 460, kind="first", rows=4, tokens=0, retired=0,
             expert_tokens=160, expert_tokens_max=20, experts_touched=30, forwards=0,
             commit_forwards=0, blocks_committed=0, revealed=0, blocks_read=0),
    ]
    return {"kind": "serve", "sizes": SIZES, "chips": 1, "device_kind": "TPU v5 lite",
            "window_s": 2.0, "trace": trace, "trace_window": (0.0, 1000 * ms),
            "op_scopes": scopes, "serving_spans": spans, "program_name": "step",
            "prefill_program_name": "prefill_chunk", "decode_chunk": 4, "kv_block_t": 4,
            "prompt_len_in_window": [10, 37], "n_out_in_window": [22, 22]}


def read(name, obs):
    return harness.load_reader(name)(obs)


def test_every_new_reader_reads_the_hand_made_obs():
    obs = hand_made_obs()
    peak, bw = 197e12, 819e9
    assert read("commit_forward_share.serve", obs) == pytest.approx(100 * 5 / 28)
    assert read("tokens_per_forward.serve", obs) == pytest.approx(23 / 28)
    # the head and the unmasking of both dispatches over their 200 ms
    assert read("unmask_share.serve", obs) == pytest.approx(100 * 0.030 / 0.2)
    work = sum(sdar_cost.prefill_flops(SIZES, p) + sdar_cost.generate_flops(SIZES, p, 22)
               for p in (10, 37))
    assert read("mfu.serve.sdar", obs) == pytest.approx(100 * work / (2.0 * peak))
    # two deliveries of 4 passes: 27.5 experts touched and 30 pages a pass
    pass_s = 0.1 / 4
    need = sdar_cost.pass_bytes(SIZES, 220 / 8, 240 / 8, 4)
    assert read("decode_roofline.serve.sdar", obs) == pytest.approx(100 * need / bw / pass_s)
    # two executions of 4 passes over 2 layers; the kernels took 8 + 12 ms
    cost = sdar_cost.block_attention_cost(SIZES, 2 * 4 * 2 * 30.0, 4)
    least = max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("block_attention_roofline.serve", obs) == pytest.approx(100 * least / 0.020)
    # the grouped kernel's 30 ms and the 5 ms under moe_experts
    cost = sdar_cost.grouped_matmul_cost(SIZES, 8 * 900 / 8, 8 * 220 / 8)
    least = max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("moe_experts_roofline.serve.sdar", obs) == pytest.approx(100 * least / 0.035)


def test_the_accepted_readers_the_cell_shares_read_it_unchanged():
    """``moe_share.serve`` and ``expert_load_skew.serve`` (the MiMo cell's)
    and ``decode_view_block_share.serve`` (every serve cell's) find what
    they read in this family's obs: the same scopes, the same counters."""
    obs = hand_made_obs()
    # router 2, experts 5 + the grouped kernel 30, combine 3, the prefill's sort 5
    assert read("moe_share.serve", obs) == pytest.approx(100 * 0.045 / 0.25)
    assert read("expert_load_skew.serve", obs) == pytest.approx(
        150 * SIZES["held_experts"] / 1060)
    assert read("decode_view_block_share.serve", obs) == pytest.approx(100 * 24 / 64)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) | {"moe_share.serve", "expert_load_skew.serve",
                       "decode_view_block_share.serve"} <= listed
    for name in NEW:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "serve_ms_per_token_p50"


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Another configuration's run, the parent's run (it has no such family
    and cannot run the cell: no such stats, no such scopes) and a run with
    no trace: None, never 0 and never an exception."""
    gpt = {"kind": "serve", "sizes": {"n_layer": 2}, "prompt_len_in_window": [3],
           "n_out_in_window": [2], "window_s": 1.0, "chips": 1, "device_kind": "TPU v5 lite",
           "program_name": "step", "decode_chunk": 16, "kv_block_t": 16}
    assert read(name, gpt) is None
    assert read(name, {"kind": "train"}) is None
    bare = {k: v for k, v in hand_made_obs().items()
            if k not in ("trace", "trace_window", "serving_spans", "op_scopes",
                         "prompt_len_in_window")}
    assert read(name, bare) is None
    if name != "mfu.serve.sdar":
        unlabelled = hand_made_obs()
        unlabelled["op_scopes"] = {}
        unlabelled["trace"].device_ops["/device:TPU:0"] = [Event("fusion.9", 10e6, 20e6)]
        unlabelled["serving_spans"] = [
            s._replace(stats={"rows": 16, "view_blocks": 4, "max_blocks": 16})
            for s in unlabelled["serving_spans"]]
        assert read(name, unlabelled) is None


# -- sdar_cost against counts by hand ---------------------------------------------------

S = {"d_model": 8, "n_heads": 4, "kv_heads": 2, "head_dim": 3, "n_layers": 2, "d_ff_expert": 5,
     "n_experts": 6, "experts_per_token": 2, "vocab_size": 7, "block_len": 4, "denoise_steps": 4}


def test_sdar_cost_against_counts_by_hand():
    attention = 2 * 8 * 4 * 3 + 2 * 8 * 2 * 3
    assert sdar_cost.attention_params(S) == attention == 288
    assert sdar_cost.expert_params(S) == 120 and sdar_cost.head_params(S) == 56
    dense = 2 * (attention + 8 * 6)
    assert sdar_cost.dense_params(S) == dense == 672
    token = dense + 2 * 2 * 120
    assert sdar_cost.token_params(S) == token
    per_key = 4 * 4 * 3 * 2                              # 2 products x 2, 12 wide, 2 layers
    # a prompt of 11: two whole blocks; block 0 sees 4 keys, block 1 sees 8
    assert sdar_cost.prefill_flops(S, 11) == 2 * token * 8 + per_key * (4 * 4 + 4 * 8)
    assert sdar_cost.prefill_flops(S, 3) == 0
    # a pass at cursor 8: 4 positions through everything and the head, 12 keys each
    one = 2 * (token + 56) * 4 + per_key * 4 * 12
    assert sdar_cost.pass_flops(S, 8) == one
    # 11 + 6 new: the tail of 3 and 6 tokens are 9 positions: 3 blocks at 8, 12, 16
    assert sdar_cost.blocks_of(S, 11, 6) == 3 and sdar_cost.blocks_of(S, 8, 8) == 2
    assert sdar_cost.generate_flops(S, 11, 6) == 5 * sum(
        sdar_cost.pass_flops(S, c) for c in (8, 12, 16))
    assert sdar_cost.page_bytes(S, 16) == 2 * 16 * 6 * 2
    router = 2 * 8 * 6
    assert sdar_cost.pass_bytes(S, 9, 10, 16) == (
        2 * (dense - router + 56) + 4 * router + 2 * 120 * 9 + 2 * 384 * 10)
    assert sdar_cost.block_attention_cost(S, 10, 16) == {
        "flops": 4.0 * 12 * 4 * 16 * 10, "bytes": 3840.0}
    assert sdar_cost.grouped_matmul_cost(S, 7, 3) == {
        "flops": 2.0 * 120 * 7, "bytes": 3 * 120 * 2 + 7 * (2 * 8 * 2 + 2 * 5 * 4 + 5 * 2 + 8 * 4)}
    # the cell's own numbers: a page is 32,768 B, a layer's experts 1.208 GB
    full = sdar_serve.sizes_of(json.loads(
        (harness.ROOT / "benchmark/configs/sdar-30b-a3b.json").read_text()))
    assert sdar_cost.page_bytes(full, 16) == 32768
    assert sdar_cost.expert_params(full) * 128 == 603_979_776
    assert sdar_cost.attention_params(full) == 18_874_368
    # a pass that touches every expert reads 8.1 GB
    assert 8.0e9 < sdar_cost.pass_bytes(full, 6 * 128, 0, 16) < 8.2e9


# -- the two-stream reading against the reference's own naive forward -------------------

def test_two_streams_agree_with_a_whole_forward_a_pass():
    """The check's two streams (the final sequence, and a variant a block
    and pass that sees the final stream's earlier blocks and itself)
    against :func:`ref.forward` over each pass's whole input, at toy size."""
    seed = 2**31 + 11
    layers = [weights_sdar.layer_canonical(seed, SIZES, i) for i in range(SIZES["n_layers"])]
    top = weights_sdar.top_canonical(seed, SIZES)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 1999, 10).tolist()
    tokens, marks = ref.generate(SIZES, layers, top, prompt, 9)
    seq = ref.passes_of(SIZES, prompt, tokens, marks)
    x, xv = top["embedding"][jnp.asarray(seq["final"])], top["embedding"][jnp.asarray(seq["ids"])]
    for w in layers:
        x, xv = ref.two_streams(SIZES, w, x, xv, jnp.asarray(seq["block"], jnp.int32))
    got = np.asarray(ref.logits_at(SIZES, top, xv))
    for n, (b, ids) in enumerate(zip(seq["block"], seq["ids"])):
        whole = np.concatenate([seq["final"][:4 * b], ids])
        want = np.asarray(ref.forward(SIZES, layers, top, whole))[-4:]
        assert np.abs(got[n] - want).max() < 1e-4 * np.abs(want).max(), n
    # and the pass the program revealed each token at is the reference's own choice
    conf = np.asarray(ref.confidence(jnp.asarray(got))[1])
    for n in range(len(seq["ids"])):
        best = np.where(seq["masked"][n], conf[n], -np.inf).argmax()
        assert seq["shown"][n][best]
