"""The EvaByte cell at toy size on the CPU: a sound run is ``correct``, the
float8 control and each of the three faults read over the limit; the seven
new readers on a hand-made ``obs``; ``eva_cost`` against counts by hand; the
reference against an independent dense-mask reading of the equations."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import eva_cost, harness, traffic, weights_eva
from benchmark.metrics import _spans
from benchmark.reference import evabyte as ref
from benchmark.runners import eva_serve
from benchmark.trace_reduce import Event, Trace

from . import toy_eva


@pytest.fixture(scope="module")
def readings():
    """One warmed toy server: a sound run's gaps and every variant's."""
    cell = toy_eva.cell()
    server = eva_serve.Server(cell, jax.devices()[:1])
    sizes = server.sizes
    drive = eva_serve.EvaDrive(
        server, traffic.arrivals(cell.mix, sizes["vocab_size"], cell.seed, cell.seconds),
        cell.seconds)
    drive.run()
    obs = drive.observations()
    picks = eva_serve.sample_requests(drive, cell.seed, 12)
    bad = eva_serve.malformed(drive, sizes["vocab_size"])
    longest = max(len(drive.replies[i]) for i in picks)
    server.close()
    served, wrong = eva_serve.reference_gaps(drive, picks, cell.seed, sizes,
                                             eva_serve.VARIANTS)
    return {"served": float(served.max()), "malformed": bad, "obs": obs, "longest": longest,
            **{name: float(g.max()) for name, g in wrong.items()}}


def test_a_sound_run_is_under_the_limit_and_well_formed(readings):
    assert readings["malformed"] == 0
    assert readings["served"] <= toy_eva.LIMITS["served_logit_gap_sd"]
    # the check reaches requests that left several windows behind
    assert readings["longest"] > 3 * toy_eva.CONFIG["window_size"]
    assert readings["obs"]["local_ring_blocks"] == 4 * (8 + 4)


@pytest.mark.parametrize("who", ["control_fp8", "fault_no_remote", "fault_mean_pool",
                                 "fault_stale_rollover"])
def test_the_control_and_every_fault_read_over_the_limit(readings, who):
    assert readings[who] > toy_eva.LIMITS["served_logit_gap_sd"], readings


def test_the_run_reports_the_contract_line(capsys):
    cell = toy_eva.cell()
    out = eva_serve.run(cell, jax.devices()[:1], time.perf_counter())
    assert all(v <= lim for _, v, lim in out.checks), out.checks
    harness.emit(cell, out, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"serve_ms_per_token_p50", "serve_ms_per_token_p95", "setup_s"} <= set(line["metrics"])
    assert out.obs["prefill_program_name"] == "prefill_chunk"


def test_limit_readings_give_the_program_and_every_variant_a_row():
    cell = toy_eva.cell(seconds=1.0)
    rows = list(eva_serve.limit_readings(cell, jax.devices()[:1], [cell.seed, cell.seed + 7], 1))
    who = [r["who"] for r in rows]
    assert who == ["control_fp8", "fault_no_remote", "fault_mean_pool",
                   "fault_stale_rollover", "program", "program"]
    limit = toy_eva.LIMITS["served_logit_gap_sd"]
    assert all((r["served_logit_gap_sd"] <= limit) == (r["who"] == "program") for r in rows), rows
    assert all(r["malformed_replies"] == 0 for r in rows if r["who"] == "program")


# -- the readers on a hand-made obs ----------------------------------------------

SIZES = eva_serve.sizes_of(toy_eva.CONFIG)
NEW = ["mfu.serve.eva", "decode_roofline.serve.eva", "eva_decode_attention_roofline.serve",
       "attn_eva_share.serve", "eva_summarise_share.serve", "eva_summary_read_share.serve",
       "local_kv_block_share.serve"]


def hand_made_obs():
    """A traced window of 1 s: two decode chunks of 100 ms and one prefill
    chunk of 50 ms on the device; operations under each scope; the
    engine's regions with their stats."""
    ms = 1e6
    kernel = "%paged_decode_attention.{} = f32[4,4,32]{{2,1,0}} custom-call(%a, %b)"
    ops = [(kernel.format(1), "jit(step)/while/body/attn_eva/eva_local/pallas_call", 10, 20),
           (kernel.format(2), "jit(step)/while/body/attn_eva/eva_remote/pallas_call", 30, 10),
           ("fusion.3", "jit(step)/while/body/attn_eva/mul", 40, 5),
           ("fusion.4", "jit(step)/while/body/eva_summarise/gather", 50, 8),
           ("fusion.5", "jit(step)/while/body/mlp/dot", 60, 30),
           (kernel.format(1), "", 210, 25),
           ("fusion.4", "", 250, 12),
           ("fusion.6", "jit(prefill_chunk)/attn_eva/chunk_attention", 400, 20),
           ("fusion.7", "jit(prefill_chunk)/eva_summarise/dot", 430, 5)]
    scopes = {name: path for name, path, _, _ in ops if path}
    trace = Trace(
        device_ops={"/device:TPU:0": [Event(n, s * ms, d * ms) for n, _, s, d in ops]},
        device_modules={"/device:TPU:0": [Event("jit_step(1)", 0, 100 * ms),
                                          Event("jit_step(1)", 200 * ms, 100 * ms),
                                          Event("jit_prefill_chunk(2)", 400 * ms, 50 * ms)]},
        device_async={}, host_spans=[])
    span = lambda name, at, **stats: _spans.Span(name, at * ms, ms, "t#0", stats)
    spans = [
        span("serving.engine.dispatch", 1, rows=16, live=2, local_blocks=10, summary_blocks=6,
             local_blocks_read=9, summary_blocks_read=3, rollovers=0, view_blocks=4,
             max_blocks=16),
        span("serving.engine.dispatch", 201, rows=16, live=3, local_blocks=14, summary_blocks=8,
             local_blocks_read=7, summary_blocks_read=5, rollovers=1, view_blocks=8,
             max_blocks=16),
        span("serving.engine.deliver", 110, kind="chunk", rows=16, tokens=8, summaries_written=2),
        span("serving.engine.deliver", 460, kind="first", rows=1, tokens=1, summaries_written=9),
    ]
    return {"kind": "serve", "sizes": SIZES, "chips": 1, "device_kind": "TPU v5 lite",
            "window_s": 2.0, "trace": trace, "trace_window": (0.0, 1000 * ms),
            "op_scopes": scopes, "serving_spans": spans, "program_name": "step",
            "prefill_program_name": "prefill_chunk", "decode_chunk": 4, "kv_block_t": 4,
            "prompt_len_in_window": [10, 70], "n_out_in_window": [40, 40],
            "local_ring_blocks": 48}


def read(name, obs):
    return harness.load_reader(name)(obs)


def test_every_new_reader_reads_the_hand_made_obs():
    obs = hand_made_obs()
    programs_s = 0.25
    # both kernels and the join under attn_eva in both decode chunks, and the prefill's
    assert read("attn_eva_share.serve", obs) == pytest.approx(100 * 0.080 / programs_s)
    assert read("eva_summarise_share.serve", obs) == pytest.approx(100 * 0.025 / programs_s)
    assert read("eva_summary_read_share.serve", obs) == pytest.approx(100 * 8 / 24)
    assert read("local_kv_block_share.serve", obs) == pytest.approx(100 * 12 / 48)
    # the accepted reader of the dispatch every family shares: the table's view
    assert read("decode_view_block_share.serve", obs) == pytest.approx(100 * 12 / 32)
    peak, bw = 197e12, 819e9
    work = sum(eva_cost.prefill_flops(SIZES, p) + eva_cost.decode_flops(SIZES, p, 40)
               for p in (10, 70))
    assert read("mfu.serve.eva", obs) == pytest.approx(100 * work / (2.0 * peak))
    # a dispatch's last step reads (9 + 7) / 2 local and (3 + 5) / 2 summary pages of 4 rows
    step_s = 0.1 / 4
    need = eva_cost.decode_step_bytes(SIZES, 4 * 8.0, 4 * 4.0)
    assert read("decode_roofline.serve.eva", obs) == pytest.approx(100 * need / bw / step_s)
    # two executions of 4 steps over 2 layers; the kernels took 20 + 10 + 25 ms
    cost = eva_cost.decode_attention_cost(SIZES, 2 * 4 * 2 * 4 * 12.0)
    least = max(cost["flops"] / peak, cost["bytes"] / bw)
    assert read("eva_decode_attention_roofline.serve", obs) == pytest.approx(100 * least / 0.055)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """Another configuration's run, the parent's run of this one (no such
    stats on its dispatches, no such scopes) and a run with no trace: None,
    never 0 and never an exception."""
    gpt = {"kind": "serve", "sizes": {"n_layer": 2}, "prompt_len_in_window": [3],
           "n_out_in_window": [2], "window_s": 1.0, "chips": 1, "device_kind": "TPU v5 lite",
           "program_name": "step", "decode_chunk": 16, "kv_block_t": 16}
    assert read(name, gpt) is None
    assert read(name, {"kind": "train"}) is None
    bare = {k: v for k, v in hand_made_obs().items()
            if k not in ("trace", "trace_window", "serving_spans", "op_scopes",
                         "prompt_len_in_window")}
    assert read(name, bare) is None
    if name != "mfu.serve.eva":
        unlabelled = hand_made_obs()
        unlabelled["op_scopes"] = {}
        unlabelled["trace"].device_ops["/device:TPU:0"] = [
            Event("fusion.9", 10e6, 20e6)]
        unlabelled["serving_spans"] = [
            s._replace(stats={"rows": 16, "view_blocks": 4, "max_blocks": 16})
            for s in unlabelled["serving_spans"]]
        assert read(name, unlabelled) is None


# -- eva_cost against counts by hand ---------------------------------------------------

S = {"d_model": 8, "n_heads": 2, "head_dim": 3, "d_ff": 5, "n_layers": 2, "window": 8,
     "chunk_size": 2, "vocab_size": 7}


def test_eva_cost_against_counts_by_hand():
    layer = 4 * 8 * 6 + 3 * 8 * 5
    assert eva_cost.layer_params(S) == layer == 312 and eva_cost.head_params(S) == 56
    # position 5: 6 keys of its own window; position 8: its own key and the
    # 4 summaries of window 0; position 19: 4 keys and the 8 summaries of two windows
    assert [eva_cost.keys_seen(S, p) for p in (0, 5, 7, 8, 19)] == [1, 6, 8, 5, 12]
    per_key = 4 * 6 * 2                              # 2 products x 2 x width, 2 layers
    span = sum(eva_cost.keys_seen(S, p) for p in range(3, 20))
    assert eva_cost.attention_flops_span(S, 3, 17) == per_key * span
    assert eva_cost.attention_flops_span(S, 3, 0) == 0
    assert eva_cost.prefill_flops(S, 9) == 2 * 2 * layer * 9 + per_key * sum(
        eva_cost.keys_seen(S, p) for p in range(9)) + 2 * 56
    assert eva_cost.decode_flops(S, 9, 4) == 3 * 2 * (2 * layer + 56) + per_key * sum(
        eva_cost.keys_seen(S, p) for p in range(9, 12))
    assert eva_cost.kv_bytes_per_row(S) == 2 * 6 * 2
    assert eva_cost.decode_step_bytes(S, 100, 10) == 2 * (2 * layer + 56) + 2 * 24 * 110
    assert eva_cost.decode_attention_cost(S, 50) == {"flops": 4 * 6 * 50, "bytes": 24 * 50}
    # the cell's own numbers: a page of 16 rows is 262,144 B at the published widths
    full = eva_serve.sizes_of(json.loads((harness.ROOT / "benchmark/configs/evabyte.json").read_text()))
    assert 16 * eva_cost.kv_bytes_per_row(full) == 262144
    assert eva_cost.layer_params(full) == 4 * 4096 ** 2 + 3 * 4096 * 11008


# -- the reference against a dense-mask reading of the equations ------------------------

def dense_attention(s, w, h):
    """The attention of ISSUE 32's section 1 written as ONE masked matrix
    over every key and every summary of the sequence, in float64 numpy: no
    windows looped over, no blocks."""
    L, H, d, W, C = h.shape[0], s["n_heads"], s["head_dim"], s["window"], s["chunk_size"]
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    h = np.asarray(h, np.float64)

    def rotary(x):
        half = d // 2
        freqs = 1.0 / (s["rope_theta"] ** (np.arange(half) / half))
        ang = np.arange(L)[:, None, None] * freqs
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    q, k = (rotary((h @ w[n]).reshape(L, H, d)) for n in ("wq", "wk"))
    v = (h @ w["wv"]).reshape(L, H, d)
    scale = d ** -0.5
    soft = lambda x, axis: np.exp(x - x.max(axis, keepdims=True)) / np.exp(
        x - x.max(axis, keepdims=True)).sum(axis, keepdims=True)
    kc, vc = k.reshape(L // C, C, H, d), v.reshape(L // C, C, H, d)
    ks = np.einsum("nch,nchd->nhd", soft(scale * np.einsum("nchd,hd->nch", kc, w["mu"]), 1), kc)
    vs = np.einsum("nch,nchd->nhd", soft(scale * np.einsum("nchd,hd->nch", kc, w["phi"]), 1), vc)
    t, j, c = np.arange(L)[:, None], np.arange(L)[None, :], np.arange(L // C)[None, :]
    local = (j // W == t // W) & (j <= t)
    remote = C * c + C - 1 < W * (t // W)
    scores = np.concatenate([np.einsum("thd,jhd->htj", q, k), np.einsum("thd,chd->htc", q, ks)], -1)
    scores = np.where(np.concatenate([local, remote], -1)[None], scale * scores, -np.inf)
    p = soft(scores, -1)
    out = np.einsum("htj,jhd->thd", p[..., :L], v) + np.einsum("htc,chd->thd", p[..., L:], vs)
    return out.reshape(L, H * d) @ w["wo"]


def test_the_reference_agrees_with_a_dense_mask_reading_of_the_equations():
    seed, L = 2**31 + 11, 4 * SIZES["window"]
    w = weights_eva.layer_canonical(seed, SIZES, 0)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (L, SIZES["d_model"])))
    want = dense_attention(SIZES, w, h)
    got = np.asarray(ref.attention(SIZES, w, jnp.asarray(h), None, None))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # and each fault moves it: the masks the faults change are in it
    for fault in ("no_remote", "mean_pool", "stale_rollover"):
        wrong = np.asarray(ref.attention(SIZES, w, jnp.asarray(h), None, fault))
        assert np.abs(wrong - want).max() > 0.05 * np.abs(want).max(), fault
        # none of them touches the first window, which sees no summary
        assert np.abs(wrong - want)[:SIZES["window"]].max() < 1e-4 * np.abs(want).max()
