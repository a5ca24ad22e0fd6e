"""``ops/head_choice.py`` against ``models/sdar.choose`` over logits written
out, on the CPU in interpret mode: every way the vocabulary can lie over the
tiles (a whole number of them, one column short, 128 times a prime, under
one tile), ties, and the shape the kernel leaves to the written-out form."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import sdar
from kubeflow_tpu.ops import head_choice as hc
from kubeflow_tpu.ops.fallback import reset_fallback_warnings
from kubeflow_tpu.runtime.metrics import METRICS

TILE = 256
CFG = sdar.SdarConfig.tiny()
B = CFG.block_len

# name: (rows, d, vocabulary)
CASES = {
    "whole_tiles": (16, 64, 4 * TILE),
    "one_column_short": (16, 64, 4 * TILE - 1),
    "lanes_times_a_prime": (16, 64, 128 * 11),
    "one_tile": (12, 32, TILE),
    "under_a_tile": (12, 64, 96),
    "one_column_past_a_tile": (8, 32, TILE + 1),
}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(hc, "_VOCAB_TILE", TILE)


def operands(case, dtype, scale=0.5):
    rows, d, vocab = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    x = jax.random.normal(keys[0], (rows, d), jnp.float32).astype(dtype)
    head = (jax.random.normal(keys[1], (d, vocab), jnp.float32) * scale).astype(dtype)
    return x, head


def chosen(x, head):
    """``choose`` at temperature 0 over the logits written out: ids and
    confidences, a row each."""
    logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
    slots = x.shape[0] // B
    ids, conf = sdar.choose(CFG, logits, jnp.zeros((slots,), jnp.float32),
                            jnp.zeros((slots, 2), jnp.uint32))
    return np.asarray(ids).reshape(-1), np.asarray(conf).reshape(-1), np.asarray(logits)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_walk_chooses_what_choose_does_over_written_logits(case, dtype):
    x, head = operands(case, dtype)
    ids, top, lse = hc.head_choice(x, head)
    assert (ids.dtype, top.dtype, lse.dtype) == (jnp.int32, jnp.float32, jnp.float32)
    want_ids, want_conf, logits = chosen(x, head)
    assert list(np.asarray(ids)) == list(want_ids)
    np.testing.assert_allclose(np.asarray(jnp.exp(top - lse)), want_conf, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top), logits.max(-1), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["whole_tiles", "lanes_times_a_prime", "under_a_tile"])
def test_a_tie_goes_to_the_lower_id_inside_a_tile_and_between_tiles(case):
    """Two columns of the head made equal give two equal logits to the last
    bit: the lower id wins, be the pair inside one tile or in two."""
    rows, d, vocab = CASES[case]
    x, head = operands(case, jnp.float32, scale=0.05)
    peak = x.T / jnp.linalg.norm(x, axis=1) * 4.0              # column r: row r's own direction
    pairs = [(3, 7), (5, vocab - 1), (vocab - 3, vocab - 2)]   # one tile; the first and the last; the last
    for r, (low, high) in enumerate(pairs):
        head = head.at[:, low].set(peak[:, r]).at[:, high].set(peak[:, r])
    ids, top, lse = hc.head_choice(x, head)
    logits = np.asarray(jnp.dot(x, head, preferred_element_type=jnp.float32))
    for r, (low, high) in enumerate(pairs):
        assert logits[r, low] == logits[r, high] == logits[r].max()
        assert int(ids[r]) == low == int(np.argmax(logits[r]))


@pytest.mark.parametrize("case", list(CASES))
def test_a_row_of_equal_logits_takes_id_0_at_a_share_of_one_in_vocab(case):
    rows, d, vocab = CASES[case]
    x = jnp.ones((rows, d), jnp.float32)
    head = jnp.full((d, vocab), 0.25, jnp.float32)
    ids, top, lse = hc.head_choice(x, head)
    assert not np.asarray(ids).any()
    np.testing.assert_allclose(np.asarray(jnp.exp(top - lse)), 1.0 / vocab, rtol=1e-5)


def test_rows_that_do_not_fit_the_fast_memory_take_the_written_out_form(monkeypatch):
    x, head = operands("whole_tiles", jnp.bfloat16)
    monkeypatch.setattr(hc, "_VMEM_BUDGET", 1 << 10)
    reset_fallback_warnings()
    before = METRICS.value("ops_fused_fallback_total", kernel="head_choice")
    with pytest.warns(RuntimeWarning, match="head_choice"):
        got = hc.head_choice(x, head)
    assert METRICS.value("ops_fused_fallback_total", kernel="head_choice") == before + 1
    want = hc.materialised(x, head)
    assert all(np.array_equal(np.asarray(g), np.asarray(w)) for g, w in zip(got, want))


def test_operands_of_two_types_are_refused():
    x, head = operands("one_tile", jnp.bfloat16)
    with pytest.raises(ValueError, match="do not match"):
        hc.head_choice(x.astype(jnp.float32), head)
    with pytest.raises(ValueError, match="do not match"):
        hc.head_choice(x[:, :16], head)
