"""Peaks, shape-based counts and the traffic generator against hand-worked
numbers."""

import numpy as np
import pytest

from benchmark import flops, peaks, traffic


def test_peaks_table_knows_v5e_and_nothing_else():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(RuntimeError, match="not in the benchmark's peaks"):
        peaks.peaks_for("cpu")


def test_train_flops_per_token_gpt2_medium_by_hand():
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) = 301,989,888 block weights,
    # + 1024 x 50257 head = 353,453,056 -> x6 = 2,120,718,336;
    # attention: 24 layers x 4 x 512.5 x 1024 = 50,380,800 forward -> x3.
    want = 6 * 353_453_056 + 3 * 50_380_800
    assert flops.train_flops_per_token(24, 1024, 4096, 50257, 1024) == want


def test_flash_costs_by_hand():
    # b8 h16 L1024 d64: triangle 524,800 pairs; forward 2 matmuls x 2 FLOPs
    # x 64 x 128 heads = 17,196,646,400; backward 5 matmuls.
    fwd = flops.flash_fwd_cost(8, 16, 1024, 64)
    assert fwd["flops"] == 4 * 8 * 16 * 524_800 * 64 == 17_196_646_400
    assert fwd["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2 + 4 * 8 * 16 * 1024
    bwd = flops.flash_bwd_cost(8, 16, 1024, 64)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    p = peaks.peaks_for("TPU v5 lite")
    # compute bound: 17.2 GFLOP / 197 TFLOP/s = 87.3 us > 67.6 MB / 819 GB/s
    assert flops.roofline_seconds(fwd, p) == pytest.approx(17_196_646_400 / 197e12)


def test_decode_bytes_and_serving_flops_by_hand():
    # weights 353,453,056 x 2 B; KV: 2 x 24 x 1024 x 2 B = 98,304 B a token
    assert flops.decode_step_bytes(24, 1024, 4096, 50257, 1000) == \
        2 * 353_453_056 + 98_304 * 1000
    # one-token prompt: every block weight once, attention over 1 key, the head
    assert flops.prefill_flops(24, 1024, 4096, 50257, 1) == \
        2 * 301_989_888 + 24 * 4 * 1024 + 2 * 1024 * 50257
    assert flops.decode_flops(24, 1024, 4096, 50257, 10, 1) == 0


def test_traffic_same_seed_same_inputs_and_every_seed_the_same_sizes():
    mix = {"kind": "open_loop", "rate_rps": 20, "lead_in_s": 2,
           "prompt_len": {"kind": "lognormal", "median": 128, "sigma": 0.9,
                          "min": 16, "max": 768}}
    a = traffic.arrivals(mix, 50257, 2**31 + 11, 10)
    b = traffic.arrivals(mix, 50257, 2**31 + 11, 10)
    c = traffic.arrivals(mix, 50257, 5, 10)
    assert a == b and a != c
    assert len(a) == 240 and a[0].due_s == -2.0 and a[-1].due_s < 10
    # another seed: the same times and lengths in the same order, other tokens
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.due_s for x in a] == [x.due_s for x in c]
    d = traffic.arrivals({**mix, "order": 1}, 50257, 5, 10)
    assert [len(x.prompt) for x in d] != [len(x.prompt) for x in c]
    assert sorted(len(x.prompt) for x in d) == sorted(len(x.prompt) for x in c)
    lens = [len(x.prompt) for x in a]
    assert min(lens) == 16 and max(lens) == 768 and np.median(lens) == pytest.approx(128, abs=2)
    feed = {"shape": [2, 8]}
    assert (traffic.batch(feed, 100, 7, 3) == traffic.batch(feed, 100, 7, 3)).all()
    assert (traffic.batch(feed, 100, 7, 3) != traffic.batch(feed, 100, 7, 4)).any()


def test_bursts_and_shared_prefixes_are_data():
    mix = {"kind": "open_loop", "rate_rps": 20, "lead_in_s": 0,
           "prompt_len": {"kind": "fixed", "value": 64},
           "burst": {"every_s": 2.0, "size": 8},
           "shared_prefix": {"share": 1.0, "groups": 2, "length": 32}}
    a = traffic.arrivals(mix, 1000, 1, 10)
    due = np.array([x.due_s for x in a])
    assert (np.sum(due == 2.0) >= 8) and (np.sum(due == 4.0) >= 8)
    heads = {tuple(x.prompt[:32]) for x in a}
    assert len(heads) == 2
