"""``ops/paged_attention.py`` interpreted on the CPU against a plain
``jax.numpy`` softmax over each row's own positions: ragged lengths in one
batch, pages scattered through the arena, and nothing read that a row's
length does not name."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models.mimo import _heads_apart
from kubeflow_tpu.ops.paged_attention import paged_decode_attention

KV, GROUP, BT, COLS, PAGES = 4, 2, 16, 12, 4
SPAN = PAGES * BT                                   # one page group: 64 positions
#: a dead row, one position, a page less one / whole / plus one, a group
#: less one / whole / plus one, two groups and a bit, the whole table
LENGTHS = (0, 1, 15, 16, 17, SPAN - 1, SPAN, SPAN + 1, 2 * SPAN + 9, COLS * BT)
WIDTHS = {"24x16": (24, 16), "192x128": (192, 128)}


def plain(q, keys, vals, table, lengths, scale):
    """Softmax(q . k * scale) . v over positions ``0 .. length - 1`` of each
    row's own pages, float32; zeros for a length of 0."""
    slots, heads, _ = q.shape
    dv = vals.shape[2] // KV
    out = []
    for s in range(slots):
        n = int(lengths[s])
        if n == 0:
            out.append(jnp.zeros((heads, dv), jnp.float32))
            continue
        own = table[s, :-(-n // BT)]
        k = keys[own].astype(jnp.float32).reshape(len(own) * BT, KV, -1)[:n]
        v = vals[own].astype(jnp.float32).reshape(len(own) * BT, KV, dv)[:n]
        qs = q[s].astype(jnp.float32).reshape(KV, heads // KV, -1)
        p = jax.nn.softmax(jnp.einsum("kgd,tkd->kgt", qs, k) * scale, axis=-1)
        out.append(jnp.einsum("kgt,tkd->kgd", p, v).reshape(heads, dv))
    return np.asarray(jnp.stack(out))


@functools.lru_cache(maxsize=None)
def case(widths, poison=False):
    """One batch of every length in ``LENGTHS``: (kernel's output, plain
    reference). ``poison``: every page no row's length names, and what lies
    past the length in a row's last page, holds NaN."""
    qk, dv = WIDTHS[widths]
    rng = np.random.default_rng(7)
    slots, blocks = len(LENGTHS), len(LENGTHS) * COLS
    table = rng.permutation(blocks).astype(np.int32).reshape(slots, COLS)
    keys = rng.normal(size=(blocks + 1, BT, KV * qk)).astype(np.float32)
    vals = rng.normal(size=(blocks + 1, BT, KV * dv)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(slots, KV * GROUP, qk)), jnp.bfloat16)
    lengths = np.asarray(LENGTHS, np.int32)
    for s, n in enumerate(LENGTHS):                 # a row's LAST position stands out
        if n:
            vals[table[s, (n - 1) // BT], (n - 1) % BT] += 50.0
    if poison:
        named = np.zeros((blocks + 1, BT), bool)
        for s, n in enumerate(LENGTHS):
            pos = np.arange(n)
            named[table[s, pos // BT], pos % BT] = True
        keys[~named], vals[~named] = np.nan, np.nan
    keys, vals = jnp.asarray(keys, jnp.bfloat16), jnp.asarray(vals, jnp.bfloat16)
    got = paged_decode_attention(
        _heads_apart(q.reshape(slots, KV, GROUP, qk), KV), keys, vals, jnp.asarray(table),
        jnp.asarray(lengths), scale=qk ** -0.5, kv_heads=KV, pages=PAGES)
    return np.asarray(got), plain(q, jnp.nan_to_num(keys), jnp.nan_to_num(vals), table,
                                  lengths, qk ** -0.5)


@pytest.mark.parametrize("widths", list(WIDTHS))
@pytest.mark.parametrize("row", range(len(LENGTHS)), ids=[f"len{n}" for n in LENGTHS])
def test_a_row_attends_over_its_own_positions(widths, row):
    """bfloat16 probabilities against float32: within a hundredth of the
    row's largest output. Values are N(0, 1) but for each row's last
    position, which holds 50 more: a row read one position short is off by
    its whole weight."""
    got, want = case(widths)
    assert got.dtype == np.float32 and got.shape == want.shape
    if LENGTHS[row] == 0:
        assert not got[row].any()
    assert np.abs(got[row] - want[row]).max() <= 0.01 * max(1.0, np.abs(want[row]).max())


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_nothing_is_read_that_a_length_does_not_name(widths):
    """NaN in every page outside the rows' first ``ceil(length / 16)``
    columns and past the length in a row's last page: finite, and the same
    bits as over a clean arena."""
    clean, _ = case(widths)
    dirty, _ = case(widths, poison=True)
    assert np.isfinite(dirty).all()
    assert np.array_equal(clean, dirty)


def test_a_length_past_the_table_is_cut_to_it():
    """A row past the columns it was handed reads them all and no further."""
    qk, dv = WIDTHS["24x16"]
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.permutation(8).astype(np.int32).reshape(2, 4))
    keys = jnp.asarray(rng.normal(size=(9, BT, KV * qk)), jnp.bfloat16)
    vals = jnp.asarray(rng.normal(size=(9, BT, KV * dv)), jnp.bfloat16)
    q = _heads_apart(jnp.asarray(rng.normal(size=(2, KV, GROUP, qk)), jnp.bfloat16), KV)
    run = functools.partial(paged_decode_attention, q, keys, vals, table,
                            scale=qk ** -0.5, kv_heads=KV, pages=PAGES)
    assert np.array_equal(run(jnp.asarray([4 * BT + 40, 9])), run(jnp.asarray([4 * BT, 9])))


def test_queries_must_be_as_wide_as_a_row_of_keys():
    with pytest.raises(ValueError):
        paged_decode_attention(jnp.zeros((1, 8, 24), jnp.bfloat16),
                               jnp.zeros((3, BT, 96), jnp.bfloat16),
                               jnp.zeros((3, BT, 64), jnp.bfloat16),
                               jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
                               scale=1.0, kv_heads=KV)
