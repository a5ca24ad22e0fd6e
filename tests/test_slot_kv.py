"""serving/paged.py's ``SlotKV`` — the one owner of every slot's KV on the
host — alone, with no engine and no model: the verbs the engine performs
(check, reserve, hold, bind, advance, dispatch_tables, warm_tables,
chunk_tables, release), for one kind of cache and for two, and the
contiguous twin that has nothing behind them."""

import numpy as np
import pytest

from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.serving.paged import (ContiguousKV, KVBlocksExhausted,
                                        KVReservation, SlotKV, WindowRings)

SLOTS, MAX_SEQ, BT = 3, 64, 4          # 16 columns a row: widths 4, 8, 12, 16
WINDOW, LOOKAHEAD = 8, 4               # rings of ceil((8 + 4 - 1) / 4) + 1 = 4


def owner(kinds, n_blocks=SLOTS * MAX_SEQ // BT, engine_id="0"):
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


@pytest.mark.parametrize("kinds", [1, 2])
def test_a_round_trip_leaves_both_kinds_whole(kinds):
    kv = owner(kinds)
    res = kv.reserve(9 + 20)
    assert res.total == 8 and (res.ring is not None) == (kinds == 2)
    assert kv.alloc.available() == kv.alloc.n_blocks - 8 and kv.alloc.used() == 0
    kv.hold(1, res)
    assert (kv.tables == kv.alloc.trash).all()          # held: still on trash
    (ids,) = kv.bind([1], [res], [9])
    assert ids.shape == (1, 3) and list(ids[0]) == res.granted
    assert list(kv.tables[1, :3]) == res.granted and kv.alloc.used() == 3
    for _ in range(5):
        kv.advance([1], 4)
    assert kv.alloc.used() == 8 and (kv.tables[[0, 2]] == kv.alloc.trash).all()
    if kinds == 2:
        assert 0 < kv.rings.used() <= kv.rings.cols
    kv.release(1)
    assert whole(kv)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_release_trashes_the_row_before_its_blocks_are_grantable(kind):
    """The retire order: at the moment a kind's blocks go back to the free
    list (from where the next admission may be granted them), no table of
    that kind shows any of them."""
    kv = owner(2)
    res = kv.reserve(30)
    kv.bind([0], [res], [10])
    kv.advance([0], 4)
    alloc, tables = ((kv.alloc, kv.tables) if kind == "full"
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


@pytest.mark.parametrize("kind", ["full", "window"])
def test_exhaustion_raises_and_leaves_state_unchanged(kind):
    kv = owner(2, n_blocks=10)
    held = kv.reserve(24)                      # 6 of 10 full blocks, 1 of 3 rings
    if kind == "window":
        held = [held, kv.reserve(4), kv.reserve(4)]      # all three rings
    before = (kv.alloc.available(), kv.rings.alloc.available())
    with pytest.raises(KVBlocksExhausted):
        kv.reserve(20 if kind == "full" else 4)
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


@pytest.mark.parametrize("kinds", [1, 2])
def test_warm_tables_are_all_trash_one_set_a_width(kinds):
    kv = owner(kinds)
    sets = kv.warm_tables()
    assert [np.asarray(t[0]).shape for t in sets] == [(SLOTS, w) for w in (4, 8, 12, 16)]
    for tables in sets:
        assert len(tables) == (3 if kinds == 2 else 1)
        assert (np.asarray(tables[0]) == kv.alloc.trash).all()
        if kinds == 2:
            assert np.asarray(tables[1]).shape == (SLOTS, kv.rings.cols)
            assert (np.asarray(tables[1]) == kv.rings.trash).all()
            assert not np.asarray(tables[2]).any()
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
