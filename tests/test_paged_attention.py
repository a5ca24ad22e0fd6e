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


# -- two sources under one softmax (models/evabyte.py) ---------------------------------

HEADS, DIM = 4, 16           # a KV head a query head: the kernel's whole-product branch


@functools.lru_cache(maxsize=None)
def two_sources():
    """Every query head with a KV head of its own, a local and a summary
    arena with a table each, every pair of lengths out of ``PAIRS``: (the
    joined output, a plain softmax over both sources' positions together,
    the kernel's stats a source)."""
    from kubeflow_tpu.models.evabyte import _heads_apart as apart
    from kubeflow_tpu.ops.paged_attention import join_softmax

    rng = np.random.default_rng(11)
    slots = len(PAIRS)
    arenas, tables = [], []
    for _ in range(2):
        blocks = slots * COLS
        tables.append(rng.permutation(blocks).astype(np.int32).reshape(slots, COLS))
        arenas.append((jnp.asarray(rng.normal(size=(blocks + 1, BT, HEADS * DIM)), jnp.bfloat16),
                       jnp.asarray(3 * rng.normal(size=(blocks + 1, BT, HEADS * DIM)), jnp.bfloat16)))
    q = jnp.asarray(2 * rng.normal(size=(slots, HEADS, DIM)), jnp.bfloat16)
    lengths = np.asarray(PAIRS, np.int32).T                       # [2, slots]
    parts = [paged_decode_attention(apart(q), k, v, jnp.asarray(t), jnp.asarray(n),
                                    scale=DIM ** -0.5, kv_heads=HEADS, pages=PAGES, stats=True)
             for (k, v), t, n in zip(arenas, tables, lengths)]
    want = []
    for s in range(slots):
        ks, vs = [], []
        for (k, v), t, n in zip(arenas, tables, lengths):
            own = t[s, :-(-int(n[s]) // BT)]
            ks.append(k[own].astype(jnp.float32).reshape(-1, HEADS, DIM)[:n[s]])
            vs.append(v[own].astype(jnp.float32).reshape(-1, HEADS, DIM)[:n[s]])
        ks, vs = jnp.concatenate(ks), jnp.concatenate(vs)
        if not len(ks):
            want.append(jnp.zeros((HEADS, DIM)))
            continue
        p = jax.nn.softmax(jnp.einsum("hd,thd->ht", q[s].astype(jnp.float32), ks) * DIM ** -0.5, -1)
        want.append(jnp.einsum("ht,thd->hd", p, vs))
    return np.asarray(join_softmax(*parts)), np.asarray(jnp.stack(want)), parts


#: (local length, summary length): both empty (a dead row), one source
#: empty either way, a page and a group's edges, the whole tables
PAIRS = ((0, 0), (1, 0), (0, 17), (5, 16), (SPAN, SPAN + 1), (SPAN + 9, 3), (COLS * BT, COLS * BT))


@pytest.mark.parametrize("row", range(len(PAIRS)), ids=[f"{a}+{b}" for a, b in PAIRS])
def test_two_sources_join_under_one_softmax(row):
    """The kernel called once a source with ``stats=True`` and the results
    joined: equal to ONE softmax over both sources' positions (bfloat16
    probabilities against float32: a hundredth of the row's largest
    output), zeros where neither holds anything; and each head read its OWN
    block of the value columns (the whole-product branch)."""
    got, want, parts = two_sources()
    assert got.shape == want.shape == (len(PAIRS), HEADS, DIM)
    assert np.abs(got[row] - want[row]).max() <= 0.01 * max(1.0, np.abs(want[row]).max())
    if PAIRS[row] == (0, 0):
        assert not got[row].any()
    for (out, m, l), n in zip(parts, PAIRS[row]):
        assert m.shape == l.shape == (len(PAIRS), HEADS)
        if n == 0:                      # an empty source: no mass, and it joins as nothing
            assert float(l[row].max()) == 0.0 and float(m[row].max()) < -1e29
        else:
            assert 1.0 <= float(l[row].min()) and float(l[row].max()) <= n


def test_stats_do_not_change_the_output():
    """``stats=True`` adds two outputs and moves nothing of the first."""
    qk, dv = WIDTHS["24x16"]
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.permutation(8).astype(np.int32).reshape(2, 4))
    keys = jnp.asarray(rng.normal(size=(9, BT, KV * qk)), jnp.bfloat16)
    vals = jnp.asarray(rng.normal(size=(9, BT, KV * dv)), jnp.bfloat16)
    q = _heads_apart(jnp.asarray(rng.normal(size=(2, KV, GROUP, qk)), jnp.bfloat16), KV)
    run = functools.partial(paged_decode_attention, q, keys, vals, table, jnp.asarray([37, 9]),
                            scale=qk ** -0.5, kv_heads=KV, pages=PAGES)
    assert np.array_equal(run(), run(stats=True)[0])


# -- a block of query positions a slot: ``block x heads`` rows --------------------

BLOCK = 4
#: cursors (whole blocks) of the rows: a dead row, the first block of a
#: sequence, a block at a page's start, in its middle and at its end, past a
#: page group, and at the end of the table
CURSORS = (None, 0, 16, 36, 44, SPAN + 8, COLS * BT - BLOCK)


@functools.lru_cache(maxsize=None)
def block_case():
    """Every slot brings BLOCK query positions x (KV x GROUP) heads, laid out
    as ``models/sdar.py`` does (a KV head's BLOCK x GROUP rows together), and
    reads its own pages up to ``cursor + BLOCK`` with no mask inside the
    block: (kernel's output [slots, BLOCK, heads, dv], a dense reference)."""
    qk = dv = 16
    rng = np.random.default_rng(11)
    slots, blocks = len(CURSORS), len(CURSORS) * COLS
    table = rng.permutation(blocks).astype(np.int32).reshape(slots, COLS)
    keys = jnp.asarray(rng.normal(size=(blocks + 1, BT, KV * qk)), jnp.bfloat16)
    vals = jnp.asarray(rng.normal(size=(blocks + 1, BT, KV * dv)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(slots, BLOCK, KV, GROUP, qk)), jnp.bfloat16)
    lengths = np.asarray([0 if c is None else c + BLOCK for c in CURSORS], np.int32)
    rows = jnp.swapaxes(q, 1, 2).reshape(slots, KV, BLOCK * GROUP, qk)
    got = paged_decode_attention(
        _heads_apart(rows, KV), keys, vals, jnp.asarray(table), jnp.asarray(lengths),
        scale=qk ** -0.5, kv_heads=KV, pages=PAGES)
    got = jnp.swapaxes(got.reshape(slots, KV, BLOCK, GROUP, dv), 1, 2)
    want = np.stack([plain(q[:, t].reshape(slots, KV * GROUP, qk), keys, vals, table, lengths,
                           qk ** -0.5) for t in range(BLOCK)], axis=1)
    return np.asarray(got).reshape(slots, BLOCK, KV * GROUP, dv), want


@pytest.mark.parametrize("row", range(len(CURSORS)), ids=[f"cursor{c}" for c in CURSORS])
def test_a_block_of_queries_reads_its_row_up_to_the_blocks_end(row):
    """Each of a slot's BLOCK positions against a dense softmax over ``[0,
    cursor + BLOCK)``: the block's own later positions included, whatever
    the position of the query inside it."""
    got, want = block_case()
    assert got.shape == want.shape
    if CURSORS[row] is None:
        assert not got[row].any()
    assert np.abs(got[row] - want[row]).max() <= 0.01 * max(1.0, np.abs(want[row]).max())
