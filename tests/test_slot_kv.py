"""serving/paged.py's ``SlotKV`` — the one owner of every slot's KV on the
host — alone, with no engine and no model: the verbs the engine performs
(check, reserve, hold, bind, advance, dispatch_tables, warm_tables,
chunk_tables, release), for one kind of cache, for two (full + window) and
for the other two (summary + local: a row a chunk, aligned windows), and
the contiguous twin that has nothing behind them."""

import numpy as np
import pytest

from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.serving.paged import (AlignedWindows, ContiguousKV, KVBlocksExhausted,
                                        KVReservation, SlotKV, WindowRings)

SLOTS, MAX_SEQ, BT = 3, 64, 4          # 16 columns a row: widths 4, 8, 12, 16
WINDOW, LOOKAHEAD = 8, 4               # rings of ceil((8 + 4 - 1) / 4) + 1 = 4
# the summary + local pair: aligned windows of 16 (4 blocks, and one more for
# the 3 positions a dispatch of 4 can lie past a line), a summary row a chunk
# of 2 positions: 64 / (4 * 2) = 8 columns a row, widths 2, 4, 6, 8
EVA, ALIGNED, STRIDE = "eva", 16, 2


def owner(kinds, n_blocks=SLOTS * MAX_SEQ // BT, engine_id="0"):
    if kinds == EVA:
        return SlotKV(SLOTS, MAX_SEQ, BT, n_blocks, engine_id=engine_id, stride=STRIDE,
                      rings=AlignedWindows(SLOTS, ALIGNED, BT, LOOKAHEAD, engine_id=engine_id))
    rings = (WindowRings(SLOTS, WINDOW, BT, LOOKAHEAD, engine_id=engine_id)
             if kinds == 2 else None)
    return SlotKV(SLOTS, MAX_SEQ, BT, n_blocks, engine_id=engine_id, rings=rings)


def whole(kv):
    """Nothing granted, nothing promised, every row on trash: both kinds."""
    ok = (kv.alloc.used() == 0 and kv.alloc.available() == kv.alloc.n_blocks
          and (kv.tables == kv.alloc.trash).all())
    if kv.rings is not None:
        ok = (ok and kv.rings.used() == 0
              and kv.rings.alloc.available() == kv.rings.alloc.n_blocks
              and (kv.rings.tables == kv.rings.trash).all())
    return bool(ok)


@pytest.mark.parametrize("kinds", [1, 2, EVA])
def test_a_round_trip_leaves_both_kinds_whole(kinds):
    kv = owner(kinds)
    res = kv.reserve(9 + 20)
    # 29 positions: 8 blocks of 4 rows a position, 4 of 4 rows a chunk of 2
    total, prompt = (4, 1) if kinds == EVA else (8, 3)
    assert res.total == total and (res.ring is not None) == (kinds != 1)
    assert kv.alloc.available() == kv.alloc.n_blocks - total and kv.alloc.used() == 0
    kv.hold(1, res)
    assert (kv.tables == kv.alloc.trash).all()          # held: still on trash
    (ids,) = kv.bind([1], [res], [9])
    assert ids.shape == (1, prompt) and list(ids[0]) == res.granted
    assert list(kv.tables[1, :prompt]) == res.granted and kv.alloc.used() == prompt
    for _ in range(5):
        kv.advance([1], 4)
    assert kv.alloc.used() == total and (kv.tables[[0, 2]] == kv.alloc.trash).all()
    if kinds != 1:
        assert 0 < kv.rings.used() <= kv.rings.cols
    kv.release(1)
    assert whole(kv)


@pytest.mark.parametrize("kind", ["full", "window", "summary", "local"])
def test_release_trashes_the_row_before_its_blocks_are_grantable(kind):
    """The retire order: at the moment a kind's blocks go back to the free
    list (from where the next admission may be granted them), no table of
    that kind shows any of them."""
    kv = owner(2 if kind in ("full", "window") else EVA)
    res = kv.reserve(30)
    kv.bind([0], [res], [10])
    kv.advance([0], 4)
    assert {kv.kind, kv.rings.kind} >= {kind}
    alloc, tables = ((kv.alloc, kv.tables) if kind in ("full", "summary")
                     else (kv.rings.alloc, kv.rings.tables))
    seen, release = [], alloc.release
    alloc.release = lambda r: (seen.extend(b in tables for b in r.granted),
                               release(r))
    kv.release(0)
    assert seen and not any(seen)
    assert whole(kv)


def test_advance_grants_exactly_the_frontier_and_never_past_the_reservation():
    kv = owner(1)
    res = kv.reserve(5 + 10)                  # 15 positions: 4 blocks of 4
    kv.bind([2], [res], [5])
    assert len(res.granted) == 2              # ceil(5 / 4)
    for tokens, ub in ((1, 6), (2, 8), (1, 9), (4, 13), (4, 17), (40, 57), (40, 64)):
        kv.advance([2], tokens)
        assert kv._cursor[2] == ub
        assert len(res.granted) == min(-(-ub // BT), res.total)
        assert list(kv.tables[2, :len(res.granted)]) == res.granted
        assert (kv.tables[2, len(res.granted):] == kv.alloc.trash).all()
    assert kv.alloc.used() == res.total == 4
    kv.advance([0, 1], 4)                     # rows nobody holds: nothing happens
    assert kv.alloc.used() == 4 and (kv.tables[:2] == kv.alloc.trash).all()


@pytest.mark.parametrize("rows,view,read", [
    ({}, 4, 0),                               # empty: the narrowest width
    ({0: 6, 1: 37}, 12, 3 + 11),              # one long row sets the width
    ({0: 6, 1: 37, "retired": [0, 1]}, 4, 0),     # every row dead again
], ids=["empty", "one_long_row", "every_row_dead"])
def test_dispatch_tables_picks_the_narrowest_width_and_reports_six_stats(rows, view, read):
    kv = owner(2, engine_id="stats")
    retired = rows.pop("retired", [])
    for slot, n in rows.items():
        kv.bind([slot], [kv.reserve(n + 8)], [n])
    active = [s for s in rows if s not in retired]
    for slot in retired:
        kv.release(slot)
    kv.advance(active, 4)
    tables, stats = kv.dispatch_tables(active)
    full, ring, live = (np.asarray(t) for t in tables)
    assert stats == {
        "view_blocks": view, "max_blocks": 16,
        "full_blocks": kv.alloc.used(), "window_blocks": kv.rings.used(),
        "window_blocks_unreleased": kv.rings.unreleased(),
        "full_blocks_read": read}
    assert full.shape == (SLOTS, view) and (full == kv.tables[:, :view]).all()
    assert list(live) == [s in active for s in range(SLOTS)]
    assert (ring[~live] == kv.rings.trash).all() and (ring[live] == kv.rings.tables[live]).all()
    assert METRICS.gauge("serving_decode_view_blocks", replica="stats").value == view
    if active:
        assert stats["full_blocks"] == 3 + 11 and stats["window_blocks"] > 0
        assert METRICS.gauge("serving_kv_blocks_used", replica="stats",
                             kind="full").value == 14
    else:
        assert stats["full_blocks"] == stats["window_blocks"] == 0


def test_one_kind_hands_over_the_table_alone_with_the_view_stats():
    kv = owner(1, engine_id="one")
    kv.bind([0], [kv.reserve(20)], [17])
    kv.advance([0], 2)
    tables, stats = kv.dispatch_tables([0])
    assert stats == {"view_blocks": 8, "max_blocks": 16}
    assert len(tables) == 1 and np.asarray(tables[0]).shape == (SLOTS, 8)
    assert METRICS.gauge("serving_kv_blocks_used", replica="one").value == 5


def test_a_held_row_is_dead_to_a_dispatch_until_it_is_bound():
    """A row still prefilling keeps its blocks to itself: the shared table
    shows trash, its ring is masked and it counts as not live."""
    kv = owner(2)
    res = kv.reserve(12 + 4)
    kv.hold(2, res)
    kv.chunk_tables(2, 0, 8, 8)
    assert kv.alloc.used() == 2 and kv.rings.used() > 0
    tables, stats = kv.dispatch_tables([])
    full, ring, live = (np.asarray(t) for t in tables)
    assert (full == kv.alloc.trash).all() and (ring == kv.rings.trash).all()
    assert not live.any() and stats["view_blocks"] == 4
    assert stats["full_blocks_read"] == 0 and stats["full_blocks"] == 2


def test_check_refuses_what_can_never_fit():
    kv = owner(1, n_blocks=6)
    kv.check(24)                               # 6 blocks: fits when empty
    with pytest.raises(ValueError, match="needs 7 KV blocks; the arena has 6"):
        kv.check(25)
    assert whole(kv)


@pytest.mark.parametrize("kind", ["full", "window", "summary", "local"])
def test_exhaustion_raises_and_leaves_state_unchanged(kind):
    eva = kind in ("summary", "local")
    kv = owner(EVA if eva else 2, n_blocks=5 if eva else 10)
    held = kv.reserve(24)                      # 6 of 10 full blocks (3 of 5 summary), 1 of 3 rings
    if kind in ("window", "local"):
        held = [held, kv.reserve(4), kv.reserve(4)]      # all three rings
    before = (kv.alloc.available(), kv.rings.alloc.available())
    with pytest.raises(KVBlocksExhausted):
        kv.reserve(20 if kind in ("full", "summary") else 4)
    # a ring that cannot be had gives the full kind's promise back
    assert (kv.alloc.available(), kv.rings.alloc.available()) == before
    assert kv.alloc.used() == kv.rings.used() == 0
    for res in (held if isinstance(held, list) else [held]):
        kv.release(res)
    assert whole(kv)


@pytest.mark.parametrize("stage", ["reserved", "held", "filling", "bound"])
def test_a_failure_after_reserve_releases_what_it_took(stage):
    """Whatever an admission had taken when it failed goes back: a
    reservation that never met a slot by itself, anything later by slot."""
    kv = owner(2)
    res = kv.reserve(16 + 8)
    assert isinstance(res, KVReservation) and not whole(kv)
    if stage == "reserved":
        kv.release(res)
    else:
        kv.hold(0, res)
        if stage != "held":
            kv.chunk_tables(0, 0, 8, 8)
        if stage == "bound":
            kv.chunk_tables(0, 8, 16, 8)
            kv.bind([0], [res], [16])
        kv.release(0)
    assert whole(kv) and res.total == 0 and res.granted == []
    kv.release(0)                              # a second release finds nothing
    assert whole(kv)


def test_bind_pads_each_row_with_trash_to_the_bucket():
    """An admission group's ids for the adopt: as wide as the bucket the
    prefill padded the prompts to, each row's own blocks first."""
    kv = owner(1)
    lens = [3, 16, 9]
    ress = [kv.reserve(n + 4) for n in lens]
    (ids,) = kv.bind([2, 0, 1], ress, lens, padded=16)
    assert ids.shape == (3, 4) and ids.dtype == np.int32
    for row, (slot, res, n) in enumerate(zip([2, 0, 1], ress, lens)):
        own = -(-n // BT)
        assert len(res.granted) == own and list(ids[row, :own]) == res.granted
        assert (ids[row, own:] == kv.alloc.trash).all()
        assert list(kv.tables[slot, :own]) == res.granted
        assert kv._cursor[slot] == n
    assert len({b for res in ress for b in res.granted}) == 1 + 4 + 3


def test_chunk_tables_fill_a_row_of_its_own_until_bind():
    kv = owner(2)
    res = kv.reserve(21 + 6)
    kv.hold(1, res)
    # chunk 1: positions 0..15 of a 16-position program
    read_full, write_full, read_window, write_window = kv.chunk_tables(1, 0, 16, 16)
    assert read_full.shape == (4,) and list(read_full) == res.granted[:4]
    assert list(write_full) == res.granted[:4]
    assert (read_window == kv.rings.trash).all()           # nothing before it
    # the window kind keeps only what the next reader can still see
    ring_after = kv.rings.row(1).copy()
    assert list(write_window) == [kv.rings.block_of(1, b) for b in range(4)]
    assert write_window[0] == kv.rings.trash and write_window[-1] != kv.rings.trash
    # chunk 2: positions 16..20, the last block and a half
    read_full, write_full, read_window, write_window = kv.chunk_tables(1, 16, 21, 16)
    assert read_full.shape == (8,) and list(read_full[:6]) == res.granted
    assert (read_full[6:] == kv.alloc.trash).all()
    assert list(write_full[:2]) == res.granted[4:6]
    assert (write_full[2:] == kv.alloc.trash).all()
    assert (read_window == ring_after).all()
    assert (kv.tables == kv.alloc.trash).all()              # shared row: still trash
    kv.bind([1], [res], [21])
    assert list(kv.tables[1, :6]) == res.granted and kv._cursor[1] == 21
    kv.release(1)
    assert whole(kv)


@pytest.mark.parametrize("kinds", [1, 2, EVA])
def test_warm_tables_are_all_trash_one_set_a_width(kinds):
    kv = owner(kinds)
    sets = kv.warm_tables()
    widths = (2, 4, 6, 8) if kinds == EVA else (4, 8, 12, 16)
    assert [np.asarray(t[0]).shape for t in sets] == [(SLOTS, w) for w in widths]
    for tables in sets:
        assert len(tables) == (1 if kinds == 1 else 3)
        assert (np.asarray(tables[0]) == kv.alloc.trash).all()
        if kinds != 1:
            assert np.asarray(tables[1]).shape == (SLOTS, kv.rings.cols)
            assert (np.asarray(tables[1]) == kv.rings.trash).all()
            assert not np.asarray(tables[2]).any()
    assert whole(kv)


# -- a row a chunk, and aligned windows -------------------------------------------------

def test_the_summary_kind_reckons_check_and_reserve_in_chunks():
    """A row stands for ``stride`` positions and exists once its chunk is
    whole: ``tokens // stride`` rows, a block of 4 rows to 8 positions."""
    kv = owner(EVA, n_blocks=6)
    assert kv.kind == "summary" and kv.rings.kind == "local" and kv.max_blocks == 8
    assert [kv.blocks_for(n) for n in (0, 1, 2, 7, 8, 9, 10, 47, 48, 49, 50)] == [
        0, 0, 1, 1, 1, 1, 2, 6, 6, 6, 7]
    kv.check(49)                               # 24 rows: 6 blocks, fits when empty
    with pytest.raises(ValueError, match="needs 7 KV blocks; the arena has 6"):
        kv.check(50)
    assert kv.reserve(33).total == 4 and kv.reserve(9).total == 1
    with pytest.raises(KVBlocksExhausted):
        kv.reserve(17)                         # 2 blocks of the 1 left
    assert whole(owner(EVA))


@pytest.mark.parametrize("prompt,steps", [(5, 1), (5, 4), (14, 4), (16, 3)])
def test_the_summary_kind_grants_what_is_written_and_reads_what_is_visible(prompt, steps):
    """``advance`` grants the rows the frontier's whole chunks need (a row
    is WRITTEN when its chunk completes); ``dispatch_tables`` counts the
    pages the last step reads, which are the summaries of the windows it
    has LEFT (visible), not of what is written."""
    kv = owner(EVA, engine_id="eva-stats")
    res = kv.reserve(prompt + 40)
    kv.bind([1], [res], [prompt])
    cursor = prompt
    for _ in range(9):
        kv.advance([1], steps)
        tables, stats = kv.dispatch_tables([1])
        before, cursor = cursor, cursor + steps
        assert kv._cursor[1] == cursor and kv._origin[1] == before
        written = cursor // STRIDE                                  # rows
        assert len(res.granted) == -(-written // BT) == stats["summary_blocks"]
        last = cursor - 1                                           # the last step's position
        visible = last // ALIGNED * (ALIGNED // STRIDE)             # rows
        assert stats["summary_blocks_read"] == -(-visible // BT) <= stats["view_blocks"]
        assert visible <= written
        assert stats["local_blocks_read"] == -(-(last % ALIGNED + 1) // BT)
        assert stats["rollovers"] == int(cursor // ALIGNED > before // ALIGNED)
        assert stats["local_blocks"] == kv.rings.used() <= kv.rings.cols
        assert set(stats) == {"view_blocks", "max_blocks", "summary_blocks", "local_blocks",
                              "summary_blocks_read", "local_blocks_read", "rollovers"}
        summary, ring, live = (np.asarray(t) for t in tables)
        assert summary.shape == (SLOTS, stats["view_blocks"]) and list(live) == [False, True, False]
        assert list(summary[1, :len(res.granted)]) == res.granted
    assert METRICS.gauge("serving_kv_blocks_used", replica="eva-stats",
                         kind="summary").value == len(res.granted)
    assert METRICS.gauge("serving_kv_blocks_used", replica="eva-stats",
                         kind="local").value == kv.rings.used()
    kv.release(1)
    assert whole(kv)


@pytest.mark.parametrize("lookahead,cols", [(1, 4), (2, 5), (4, 5), (5, 5), (6, 6), (16, 8)])
def test_the_local_kind_returns_a_whole_window_at_roll_over(lookahead, cols):
    """Aligned windows of 16 in blocks of 4: ``16 / 4 + ceil((lookahead - 1)
    / 4)`` blocks a slot. Every position a dispatch reads (its window, from
    the window's start) or writes has its block; a dispatch that crosses a
    line holds the old window whole and the new one's first blocks; the
    first dispatch that starts past the line has given ALL of the old
    window back, each table entry on trash before its block returned."""
    rings = AlignedWindows(2, ALIGNED, BT, lookahead)
    assert rings.cols == cols == ALIGNED // BT + -(-(lookahead - 1) // BT)
    rings.attach(0, rings.reserve())
    seen, give_back = [], rings.alloc.give_back
    rings.alloc.give_back = lambda res, blk: (seen.append(blk in rings.tables), give_back(res, blk))
    cursor, most = 3, 0
    for _ in range(40):
        rings.advance(0, cursor, cursor + lookahead)
        held = rings._held[0]
        most = max(most, len(held))
        first = cursor // ALIGNED * (ALIGNED // BT)
        assert min(held) == first                       # nothing of an earlier window
        for p in range(cursor // ALIGNED * ALIGNED, cursor + lookahead):
            assert rings.tables[0, (p // BT) % cols] == held[p // BT]
        cursor += lookahead
    assert most <= cols and rings.used() == len(rings._held[0])
    assert seen and not any(seen)
    rings.release(0)
    assert rings.used() == 0 and rings.alloc.available() == rings.alloc.n_blocks
    assert (rings.tables == rings.trash).all()


def test_a_window_that_is_not_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="not whole blocks"):
        AlignedWindows(1, 18, 4, 4)


def test_chunk_tables_of_the_summary_and_local_kinds():
    """Prefill chunks of 8 in windows of 16: the summary table grows with
    the whole chunks written; a chunk's local blocks are kept only where a
    later chunk of its window or decode can still read them."""
    kv = owner(EVA)
    res = kv.reserve(37 + 6)
    kv.hold(1, res)
    trash = kv.rings.trash
    # [0, 8): the window goes on, so the chunk's two blocks are kept
    read_summary, read_local, write_local = kv.chunk_tables(1, 0, 8, 8)
    assert read_summary.shape == (2,) and list(read_summary[:1]) == res.granted   # 4 rows
    assert (read_local == trash).all() and (write_local != trash).all()
    kept = list(write_local)
    # [8, 16): ends ON the line: nobody reads these keys again; the first
    # chunk's blocks are read (the ring as it was) and then given back
    read_summary, read_local, write_local = kv.chunk_tables(1, 8, 16, 8)
    assert len(res.granted) == 2 and list(read_summary) == res.granted
    assert sorted(b for b in read_local if b != trash) == sorted(kept)
    assert (write_local == trash).all() and kv.rings.used() == 0
    # [16, 24) and [24, 32): the second window, kept and then not
    assert (kv.chunk_tables(1, 16, 24, 8)[2] != trash).all()
    assert (kv.chunk_tables(1, 24, 32, 8)[2] == trash).all()
    # [32, 37): the prompt's last window, read by decode: kept (one block and
    # a part), the rest of the program's chunk to trash
    read_summary, read_local, write_local = kv.chunk_tables(1, 32, 37, 8)
    assert read_summary.shape == (6,) and list(read_summary[:5]) == res.granted   # 18 rows
    assert (write_local != trash).all() and kv.rings.used() == 2
    assert (kv.tables == kv.alloc.trash).all()              # shared row: still trash
    kv.bind([1], [res], [37])
    assert list(kv.tables[1, :5]) == res.granted and kv._cursor[1] == 37
    kv.release(1)
    assert whole(kv)


def test_the_contiguous_twin_takes_nothing_and_hands_out_nothing():
    kv = ContiguousKV()
    kv.check(10 ** 9)
    res = kv.reserve(10 ** 9)
    assert res.total == 0 and res.granted == [] and res.ring is None
    kv.hold(0, res)
    assert kv.bind([0], [res], [7], 16) == ()
    kv.advance([0], 16)
    assert kv.dispatch_tables([0]) == ((), {})
    assert kv.warm_tables() == [] and kv.block_t == 0
    kv.release(0), kv.release(res)


# -- a block in flight past the cursor (``ahead``): the fourth family's kind ----------

BLOCK = 4                              # a block of 4 positions, pages of 4: a page a block


def block_owner(n_blocks=SLOTS * MAX_SEQ // BT):
    return SlotKV(SLOTS, MAX_SEQ, BT, n_blocks, engine_id="blocks", ahead=BLOCK)


@pytest.mark.parametrize("prompt,moves,granted", [(8, 4, 4), (9, 4, 5), (11, 12, 7), (3, 4, 3)],
                         ids=["tail_0", "tail_1", "three_blocks_a_dispatch", "short_prompt"])
def test_the_block_in_flight_is_granted_before_its_first_write(prompt, moves, granted):
    """``bind`` leaves the bound at the prompt's length (the device's cursor
    stands behind the prompt's whole blocks, at or under it); a dispatch
    that may move the cursor by ``moves`` positions is granted up to the
    bound + moves + the block in flight, BEFORE it snapshots the table: the
    positions ``cursor .. cursor + 3`` are written at every pass."""
    kv = block_owner()
    res = kv.reserve(prompt + 40)
    kv.bind([0], [res], [prompt])
    assert len(res.granted) == -(-prompt // BT)
    kv.advance([0], moves)
    assert len(res.granted) == granted == -(-(prompt + moves + BLOCK) // BT)
    assert list(kv.tables[0, :granted]) == res.granted
    assert (kv.tables[0, granted:] == kv.alloc.trash).all()
    kv.release(0)
    assert whole(kv)


def test_settle_brings_the_bound_down_and_gives_nothing_back():
    """The device's cursor moves by what the slots committed, at most
    ``moves`` a dispatch: the host grants from an upper bound and the engine
    settles it once an event says where the cursor stood. Later grants then
    start from the settled bound; what was granted stays."""
    kv = block_owner()
    res = kv.reserve(8 + 48)
    kv.bind([2], [res], [8])
    for _ in range(3):
        kv.advance([2], 12)                      # the bound: 8 + 36, plus the block: 12 pages
    assert len(res.granted) == 12
    kv.settle(2, 8 + 3 * 4)                      # one block a dispatch was committed
    kv.advance([2], 12)
    assert len(res.granted) == 12                # 20 + 12 + 4 = 36 positions: 9 pages, under 12
    kv.settle(2, 10_000)                         # never up
    kv.advance([2], 12)
    assert len(res.granted) == 12                # 32 + 12 + 4 = 48: 12 pages
    kv.advance([2], 12)
    assert len(res.granted) == res.total == 14   # capped at the reservation
    kv.settle(1, 4)                              # a slot that holds nothing: a no-op
    kv.release(2)
    assert whole(kv)


def test_chunk_tables_of_one_kind_name_the_view_and_the_pages_to_write():
    """A family that prefills straight into the arena and keeps no ring: a
    chunk's tables are the row's own view and the pages the chunk writes,
    trash behind the prompt's end; the shared row stays on trash until
    bind."""
    kv = block_owner()
    res = kv.reserve(23 + 9)
    kv.hold(1, res)
    view, write = kv.chunk_tables(1, 0, 16, 16)
    assert view.shape == (4,) and list(view) == res.granted[:4] and list(write) == res.granted[:4]
    view, write = kv.chunk_tables(1, 16, 23, 16)
    assert view.shape == (8,) and list(view[:6]) == res.granted[:6]
    assert list(write) == res.granted[4:6] + [kv.alloc.trash] * 2
    assert (kv.tables == kv.alloc.trash).all()
    kv.bind([1], [res], [23])
    assert list(kv.tables[1, :6]) == res.granted[:6]
    tables, stats = kv.dispatch_tables([1])
    assert len(tables) == 1 and stats == {"view_blocks": 8, "max_blocks": 16}
    kv.release(1)
    assert whole(kv)
