"""Paged KV-cache block accounting for the continuous-batching engine.

ISSUE 12: the per-slot contiguous KV cache reserved worst-case
``max_seq`` rows per slot whether a request used 20 tokens or 2000. The
paged layout keeps ONE shared arena of fixed-size blocks per layer
(``[n_blocks + 1, block_t, heads, head_dim]`` — the last row is the trash
block) and a host-side per-slot block table mapping absolute positions to
arena rows. This module owns the host-side half: the free-list allocator
that reserves capacity at admission and grants physical blocks as cursors
advance, published as ``serving_kv_blocks_{free,used}`` gauges so arena
sizing is an observable capacity knob rather than a silent OOM; the kinds
of cache that keep a ring of blocks a slot beside the block table
(:class:`WindowRings`, a sliding window; :class:`AlignedWindows`, the
current one of a row of aligned windows); and :class:`SlotKV`, the ONE owner of
every slot's KV on the host, whose verbs the engine
(``serving/continuous.py``) performs without knowing table, trash or rings.

Two-phase accounting (reserve → grant) is deliberate:

- **reserve** happens at admission and covers the request's worst case
  (``ceil((prompt + budget) / block_t)`` blocks). Admission back-pressure
  is decided here: if the arena cannot promise the blocks, the request
  stays pending (:class:`KVBlocksExhausted` is a
  :class:`~kubeflow_tpu.serving.errors.FleetSaturated` so the HTTP layer's
  503/Retry-After mapping applies unchanged) — it never admits a request
  that could later need a block the arena cannot produce, so a granted
  write can never be redirected into another slot's data.
- **grant** happens just before each dispatch and only up to the cursor
  frontier that dispatch will reach. Until granted, the reserved blocks
  stay on the free list (they count against :meth:`available`, not the
  gauges), and the slot's table entries point at the trash block.

The device-side half of the contract is the trash-block convention
(``kubeflow_tpu/ops/kv_cache.py``); the host-side half is the retire
ordering (:func:`_trash_then_return`: table row → trash BEFORE blocks return
to the free list, so stale in-flight dispatches write to trash, never into
a re-granted block).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..runtime.metrics import METRICS
from .errors import FleetSaturated


def view_widths(max_blocks: int) -> Tuple[int, ...]:
    """The widths, in block-table columns, at which a decode dispatch may
    read the arena: the quarters of a row, ascending, the whole row last.
    Few on purpose — the decode program is compiled once per width."""
    return tuple(sorted({-(-max_blocks * q // 4) for q in (1, 2, 3, 4)}))


def view_blocks(tables: np.ndarray, trash: int, widths: Tuple[int, ...]) -> int:
    """The narrowest of ``widths`` that covers every granted column of the
    host block table ``[slots, max_blocks]``: a column is live when any
    row's entry in it is not the trash block, and granted blocks are a
    prefix of their row, so the last live column is the longest row's."""
    live = np.flatnonzero((tables != trash).any(axis=0))
    last = int(live[-1]) + 1 if live.size else 0
    return next(w for w in widths if w >= last)


class KVBlocksExhausted(FleetSaturated):
    """The arena cannot reserve the blocks a request needs right now.

    Subclasses :class:`FleetSaturated` on purpose: exhaustion is admission
    back-pressure, not corruption — the engine keeps the request pending
    and retries as retirements return blocks, and if it must give up the
    HTTP layer already maps FleetSaturated to 503 + Retry-After.
    """


@dataclass
class KVReservation:
    """One slot's promised block budget: ``total`` blocks reserved, of
    which ``granted`` have been popped off the free list (in position
    order — ``granted[i]`` backs positions ``[i*block_t, (i+1)*block_t)``).
    ``ring``: the same request's ring of the window kind, where the family
    has window layers (:meth:`SlotKV.reserve` promises both together).
    """
    total: int
    granted: List[int] = field(default_factory=list)
    ring: Optional["KVReservation"] = None


class KVBlockAllocator:
    """LIFO free-list allocator over ``n_blocks`` arena rows.

    Row ``n_blocks`` (the arena's last row — callers allocate
    ``n_blocks + 1`` rows) is the trash block and is never handed out;
    :attr:`trash` exposes its id for table initialization.
    """

    def __init__(self, n_blocks: int, block_t: int, *, engine_id: str = "0",
                 kind: str = ""):
        """``kind`` ("full" / "window", "summary" / "local") labels the
        gauges of an engine that keeps two kinds of cache side by side; an
        engine with one kind leaves it empty and publishes the gauges it
        always did."""
        if n_blocks <= 0:
            raise ValueError(f"need at least one KV block, got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.block_t = int(block_t)
        self.trash = self.n_blocks
        self.engine_id = engine_id
        self._labels = {"replica": engine_id, **({"kind": kind} if kind else {})}
        self._free: List[int] = list(range(self.n_blocks))
        self._promised = 0  # reserved but not yet granted
        self._publish()

    # -- accounting ---------------------------------------------------------

    def available(self) -> int:
        """Blocks that can still be promised to new reservations."""
        return len(self._free) - self._promised

    def used(self) -> int:
        """Blocks physically granted (out of the free list)."""
        return self.n_blocks - len(self._free)

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to back ``tokens`` positions."""
        return -(-int(tokens) // self.block_t)

    # -- lifecycle ----------------------------------------------------------

    def reserve(self, n_blocks: int) -> KVReservation:
        """Promise ``n_blocks`` to one request or raise
        :class:`KVBlocksExhausted`. Impossible requests (bigger than the
        whole arena) raise ValueError — waiting would never help."""
        if n_blocks > self.n_blocks:
            raise ValueError(
                f"request needs {n_blocks} KV blocks but the arena only has "
                f"{self.n_blocks}; raise kv_blocks or shrink the request")
        if n_blocks > self.available():
            raise KVBlocksExhausted(
                f"KV arena exhausted: need {n_blocks} blocks, "
                f"{self.available()} available of {self.n_blocks}",
                retry_after_s=0.05)
        self._promised += n_blocks
        return KVReservation(total=n_blocks)

    def grant(self, res: KVReservation, upto_blocks: int) -> List[int]:
        """Materialize the reservation up to ``upto_blocks`` granted blocks
        (capped at ``res.total``); returns only the newly granted ids, in
        position order."""
        upto_blocks = min(upto_blocks, res.total)
        newly: List[int] = []
        while len(res.granted) < upto_blocks:
            blk = self._free.pop()
            self._promised -= 1
            res.granted.append(blk)
            newly.append(blk)
        if newly:
            self._publish()
        return newly

    def release(self, res: KVReservation) -> None:
        """Return a reservation's blocks (granted and promised) to the
        free list. A reservation that has a table row goes through
        :func:`_trash_then_return`, never straight here."""
        self._free.extend(res.granted)
        self._promised -= res.total - len(res.granted)
        res.granted = []
        res.total = 0
        self._publish()

    def give_back(self, res: KVReservation, block: int) -> None:
        """Return ONE granted block to the free list while its reservation
        lives on (a window layer's block that the cursor has left behind):
        the block stays promised to the reservation, which may be granted
        another. The same ordering holds as for :meth:`release`: the table
        entry goes to trash first."""
        res.granted.remove(block)
        self._free.append(block)
        self._promised += 1
        self._publish()

    def _publish(self) -> None:
        METRICS.gauge("serving_kv_blocks_free", **self._labels).set(len(self._free))
        METRICS.gauge("serving_kv_blocks_used", **self._labels).set(self.used())


def _trash_then_return(row: np.ndarray, alloc: KVBlockAllocator,
                       res: Optional[KVReservation]) -> None:
    """A slot gives one kind of cache back. Retire-ordering invariant:
    redirect the table row to TRASH before the blocks return to the free
    list. Later dispatches snapshot the trashed table, so a block
    re-granted to another slot can only be written by (a) dispatches issued
    before this retire — which execute before the new slot's adopt
    overwrites the block (device streams run in issue order) — or (b) the
    new slot itself. Never a corrupting interleave."""
    row[:] = alloc.trash
    if res is not None:
        alloc.release(res)


class WindowRings:
    """The window kind of cache: every slot keeps a RING of ``cols`` blocks
    in each window layer, logical block ``b`` (positions ``[b * block_t,
    (b + 1) * block_t)``) in column ``b % cols`` of its row of
    :attr:`tables`, and holds only the blocks a dispatch can still read or
    write: those from ``(cursor - window + 1) // block_t`` up to the
    dispatch's frontier. A block the cursor has left behind by a window
    goes back to the free list (its table entry to trash first), so a long
    row costs ``cols`` blocks where the full kind costs a block per
    ``block_t`` positions.

    ``cols = ceil((window + lookahead - 1) / block_t) + 1``: a dispatch
    that advances a cursor by up to ``lookahead`` positions reads back to
    ``cursor - window + 1`` and writes up to ``cursor + lookahead - 1``.
    With one step a dispatch that is ``ceil(window / block_t) + 1``.
    """

    kind = "window"

    def __init__(self, slots: int, window: int, block_t: int, lookahead: int,
                 *, engine_id: str = "0"):
        self.window, self.block_t = int(window), int(block_t)
        self.cols = self._columns(max(int(lookahead), 1))
        # a whole ring for every slot: a slot can never hold more
        self.alloc = KVBlockAllocator(slots * self.cols, block_t,
                                      engine_id=engine_id, kind=self.kind)
        self.trash = self.alloc.trash
        self.tables = np.full((slots, self.cols), self.trash, np.int32)
        self._res: Dict[int, KVReservation] = {}
        self._held: Dict[int, Dict[int, int]] = {}     # slot -> logical block -> id
        self._frontier = np.zeros((slots,), np.int64)  # positions a full kind would hold

    def _columns(self, lookahead: int) -> int:
        return -(-(self.window + lookahead - 1) // self.block_t) + 1

    def _first_kept(self, cursor: int) -> int:
        """The oldest logical block a step at ``cursor`` can still read."""
        return max(0, cursor - self.window + 1) // self.block_t

    def reserve(self) -> KVReservation:
        """A slot's ring, promised at admission. The arena holds one for
        every slot, so a request that has a slot has its ring."""
        return self.alloc.reserve(self.cols)

    def attach(self, slot: int, res: KVReservation) -> None:
        self._res[slot] = res
        self._held[slot] = {}
        self._frontier[slot] = 0

    def row(self, slot: int) -> np.ndarray:
        return self.tables[slot]

    def advance(self, slot: int, cursor: int, frontier: int) -> List[int]:
        """Before a dispatch that starts with the slot's cursor at
        ``cursor`` and may write positions below ``frontier``: give back
        the blocks no step from ``cursor`` on can read (:meth:`_first_kept`)
        and grant those up to the frontier. Returns the ids of the logical
        blocks ``[first kept .. last]`` now held, oldest first."""
        res, held = self._res[slot], self._held[slot]
        first = self._first_kept(cursor)
        last = (frontier - 1) // self.block_t
        for b in [b for b in held if b < first]:
            self.tables[slot, b % self.cols] = self.trash       # table first
            self.alloc.give_back(res, held.pop(b))
        for b in range(max(first, last - self.cols + 1), last + 1):
            if b not in held:
                (blk,) = self.alloc.grant(res, len(res.granted) + 1)
                held[b] = blk
                self.tables[slot, b % self.cols] = blk
        self._frontier[slot] = max(self._frontier[slot], frontier)
        return [held[b] for b in sorted(held)]

    def block_of(self, slot: int, logical: int) -> int:
        return self._held[slot].get(logical, self.trash)

    def release(self, slot: int) -> None:
        self._held.pop(slot, None)
        self._frontier[slot] = 0
        _trash_then_return(self.tables[slot], self.alloc,
                           self._res.pop(slot, None))

    def used(self) -> int:
        return self.alloc.used()

    def unreleased(self) -> int:
        """Blocks the window kind would hold had nothing been given back:
        one per ``block_t`` positions of every attached row's frontier."""
        return int(sum(-(-int(self._frontier[s]) // self.block_t) for s in self._res))

    def dispatch_stats(self, moves: Sequence[Tuple[int, int]]) -> Dict[str, int]:
        """The ``serving.engine.dispatch`` region's stats of this kind, for
        a dispatch that moves its live rows' cursors ``(from, to)``."""
        return {"window_blocks": self.used(),
                "window_blocks_unreleased": self.unreleased()}


class AlignedWindows(WindowRings):
    """The local kind of cache: windows are ALIGNED (position ``t`` lies in
    window ``t // window``), not sliding, and a slot holds the blocks of its
    CURRENT window only. All of a window's blocks go back at once, at the
    first dispatch that starts past its end (table entries to trash first).
    A dispatch that crosses the line in its middle holds both: its steps
    before the line read the old window whole, those after it write the new
    window's first blocks, and the ring has a column for each:

    ``cols = window / block_t + ceil((lookahead - 1) / block_t)``: of a
    dispatch's ``lookahead`` positions at most ``lookahead - 1`` lie past a
    line that an earlier one lies before."""

    kind = "local"

    def _columns(self, lookahead: int) -> int:
        if self.window % self.block_t:
            raise ValueError(f"an aligned window of {self.window} is not whole "
                             f"blocks of {self.block_t}")
        return self.window // self.block_t + -(-(lookahead - 1) // self.block_t)

    def _first_kept(self, cursor: int) -> int:
        return cursor // self.window * (self.window // self.block_t)

    def dispatch_stats(self, moves: Sequence[Tuple[int, int]]) -> Dict[str, int]:
        """Blocks in use, the pages the dispatch's LAST step reads of each
        live row's window, and the rows whose cursor crosses a window's end
        in this dispatch."""
        pages = lambda to: -(-((to - 1) % self.window + 1) // self.block_t)
        return {"local_blocks": self.used(),
                "local_blocks_read": sum(pages(to) for _, to in moves if to > 0),
                "rollovers": sum(to // self.window > at // self.window for at, to in moves)}


class SlotKV:
    """The one owner of every slot's KV on the host. Only this class knows
    the trash id, the table's layout (``[slots, max_blocks]``, ONE table for
    every layer of the full kind; entries default to trash, so unallocated
    positions can never hit real data), the two kinds of blocks (the
    append-only kind here, the kind that keeps a ring a slot in ``rings``
    for a family that has one), the widths a decode dispatch reads the
    table at, and the order in which a slot gives everything back.

    ``stride``: positions a ROW of the append-only kind stands for. 1 is
    the full kind (a row a position). Over 1 is the summary kind (a row a
    whole chunk of ``stride`` positions, beside :class:`AlignedWindows`): a
    row is WRITTEN when its chunk completes, so ``tokens`` positions need
    ``tokens // stride`` rows, and is READ only once the cursor has left its
    window, so what a step reads (:meth:`_rows_read`) lags what is written.

    ``ahead``: positions past a slot's cursor that a step writes and reads.
    0 where a step appends the position at its cursor. Over 0 is a family
    that works on a BLOCK of ``ahead`` positions for several steps before
    the cursor passes it: the block in flight is written many times, so its
    pages are granted before the first write (the frontier a dispatch is
    granted to is its cursor bound plus ``ahead``), and the cursor moves by
    whole blocks, by a data-dependent count a dispatch, so the engine
    brings the bound down (:meth:`settle`) once the device's cursor is known.

    A slot's life, in the engine's verbs: :meth:`check` at submit;
    :meth:`reserve` before any compute is spent; :meth:`hold` once the
    request has a slot (its row stays on trash); :meth:`bind` when the
    prompt's KV is written (the row shows its blocks from here on); every
    decode dispatch :meth:`advance`, then :meth:`dispatch_tables`;
    :meth:`release` at retirement or on any failure in between. A prompt
    that prefills straight into the arenas asks :meth:`chunk_tables` a
    chunk, between ``hold`` and ``bind``.
    """

    def __init__(self, slots: int, max_seq: int, block_t: int, n_blocks: int,
                 *, engine_id: str = "0",
                 rings: Optional[WindowRings] = None, stride: int = 1,
                 ahead: int = 0):
        self.slots, self.max_seq, self.block_t = int(slots), int(max_seq), int(block_t)
        self.engine_id, self.stride, self.ahead = engine_id, int(stride), int(ahead)
        self.rings = rings
        self.kind = ("" if rings is None else "full" if self.stride == 1 else "summary")
        self.alloc = KVBlockAllocator(n_blocks, block_t, engine_id=engine_id,
                                      kind=self.kind)
        self.max_blocks = self.max_seq // (self.block_t * self.stride)
        self.tables = np.full((self.slots, self.max_blocks), self.alloc.trash,
                              np.int32)
        # jit specialises the decode program on each of these widths
        self.view_widths = view_widths(self.max_blocks)
        self._res: Dict[int, KVReservation] = {}
        # upper bound on each slot's device cursor at the dispatch frontier
        # — spec rounds advance the real cursor by a data-dependent amount,
        # so granting tracks the bound
        self._cursor = np.zeros((self.slots,), np.int64)
        # where the latest dispatch found each cursor (its stats say how far
        # it moves them)
        self._origin = np.zeros((self.slots,), np.int64)
        # the table of a row that prefills straight into the arenas, while
        # it fills: the shared row stays on trash until bind, so decode
        # dispatches in between write nothing of this dead row into its blocks
        self._filling: Dict[int, np.ndarray] = {}

    def blocks_for(self, tokens: int) -> int:
        """Blocks of the append-only kind that ``tokens`` positions write."""
        return self.alloc.blocks_for(int(tokens) // self.stride)

    def _rows_read(self, cursor: int) -> int:
        """Rows of the append-only kind that the step BEFORE ``cursor``
        reads: every position up to its own, or the summaries of the
        windows it has left."""
        if self.stride == 1:
            return cursor
        window = self.rings.window
        return max(cursor - 1, 0) // window * (window // self.stride)

    def check(self, tokens: int) -> None:
        """ValueError for a request of ``tokens`` positions that can NEVER
        fit: waiting cannot help, so it must not pend forever behind an
        arena that is too small by construction."""
        need = self.blocks_for(tokens)
        if need > self.alloc.n_blocks:
            raise ValueError(
                f"prompt + budget needs {need} KV blocks; the arena has "
                f"{self.alloc.n_blocks} (raise kv_blocks)")

    def reserve(self, tokens: int) -> KVReservation:
        """Promise a request its worst case: the blocks ``tokens`` positions
        write of the append-only kind and, with a ring kind, one ring (a
        request that has a slot always gets one). :class:`KVBlocksExhausted`
        is back-pressure and leaves nothing taken."""
        res = self.alloc.reserve(self.blocks_for(tokens))
        if self.rings is not None:
            try:
                res.ring = self.rings.reserve()
            except Exception:
                self.alloc.release(res)
                raise
        return res

    def hold(self, slot: int, res: KVReservation) -> None:
        """``res`` is ``slot``'s from here on (:meth:`release` of the slot
        returns it); the slot's row stays on trash until :meth:`bind`."""
        self._res[slot] = res
        if res.ring is not None:
            self.rings.attach(slot, res.ring)

    def bind(self, slots: Sequence[int], reservations: Sequence[KVReservation],
             prompt_lens: Sequence[int], padded: int = 0
             ) -> Tuple[np.ndarray, ...]:
        """Grant each row the blocks its PROMPT needs (decode grants the
        rest as cursors advance), point its table row at them and set its
        cursor bound — BEFORE the dispatch that writes the prompt's KV
        snapshots the ids. Returns what that dispatch takes beside the
        rows: the ids ``[n, ceil(padded / block_t)]``, trash behind each
        row's own (``padded`` 0: as wide as the longest prompt needs)."""
        cols = self.blocks_for(padded or max(prompt_lens))
        ids = np.full((len(slots), cols), self.alloc.trash, np.int32)
        for i, (slot, res, n) in enumerate(zip(slots, reservations, prompt_lens)):
            if self._res.get(slot) is not res:
                self.hold(slot, res)
            self._filling.pop(slot, None)
            self.alloc.grant(res, self.blocks_for(n))
            ids[i, :len(res.granted)] = res.granted
            self.tables[slot, :len(res.granted)] = res.granted
            self._cursor[slot] = n
        return (ids,)

    def release(self, what: Union[int, KVReservation]) -> None:
        """Give back a slot's KV of both kinds, or a reservation that never
        met a slot."""
        if isinstance(what, KVReservation):
            self.alloc.release(what)
            if what.ring is not None:
                self.rings.alloc.release(what.ring)
            return
        self._filling.pop(what, None)
        self._cursor[what] = 0
        _trash_then_return(self.tables[what], self.alloc,
                           self._res.pop(what, None))
        if self.rings is not None:
            self.rings.release(what)

    def advance(self, active: Iterable[int], tokens: int) -> None:
        """Advance every active slot's cursor upper bound by the tokens the
        next dispatch may write and grant the blocks that frontier needs —
        BEFORE the dispatch snapshots the table. The bound (not the exact
        data-dependent cursor, which spec rounds make device-resident)
        drives granting; positions past ``res.total`` stay on trash, which
        only retired-but-undrained rows can reach."""
        for slot in active:
            res = self._res.get(slot)
            if res is None:
                continue
            cursor = int(self._cursor[slot])
            ub = min(cursor + tokens, self.max_seq)
            self._origin[slot], self._cursor[slot] = cursor, ub
            if self.rings is not None:
                self.rings.advance(slot, cursor, ub)
            self._grant_into(self.tables[slot], res, ub + self.ahead)

    def settle(self, slot: int, bound: int) -> None:
        """The device's cursor of ``slot`` is known to stay at or under
        ``bound`` at the dispatch frontier (what an earlier dispatch left,
        plus the most the dispatches in flight can move it): the bound that
        :meth:`advance` grants from comes down to it. Nothing granted goes
        back."""
        if slot in self._res:
            self._cursor[slot] = min(int(self._cursor[slot]), int(bound))

    def _grant_into(self, row: np.ndarray, res: KVReservation, tokens: int) -> None:
        """Grant ``res`` the blocks ``tokens`` positions need and put the new
        ones behind those ``row`` already shows."""
        base = len(res.granted)
        new = self.alloc.grant(res, self.blocks_for(tokens))
        row[base:base + len(new)] = new

    def dispatch_tables(self, active: Iterable[int]
                        ) -> Tuple[Tuple[Any, ...], Dict[str, int]]:
        """What a decode dispatch of the ``active`` slots takes after the
        cache, and the ``serving.engine.dispatch`` region's stats. The
        table's first columns, up to the longest granted row
        (``view_blocks``); with a ring kind also every LIVE row's ring (a
        row still prefilling keeps its ring to itself: a decode step writes
        every row's token somewhere, and a dead row's must land in trash),
        which rows are live, the blocks in use by kind, and the pages of
        the append-only kind that the last step's attention fetches
        (``ops/paged_attention``: each live row its own pages, within the
        view; :meth:`_rows_read`), then the ring kind's own."""
        view = view_blocks(self.tables, self.alloc.trash, self.view_widths)
        METRICS.gauge("serving_decode_view_blocks",
                      replica=self.engine_id).set(view)
        tables = (jnp.asarray(self.tables[:, :view]),)
        stats = {"view_blocks": view, "max_blocks": self.max_blocks}
        rings = self.rings
        if rings is not None:
            active = list(active)
            live = np.zeros((self.slots,), bool)
            live[active] = True
            stats[f"{self.kind}_blocks"] = self.alloc.used()
            stats[f"{self.kind}_blocks_read"] = sum(
                min(self.alloc.blocks_for(self._rows_read(int(self._cursor[slot]))), view)
                for slot in active)
            stats.update(rings.dispatch_stats(
                [(int(self._origin[slot]), int(self._cursor[slot])) for slot in active]))
            tables += (jnp.asarray(np.where(live[:, None], rings.tables,
                                            rings.trash)),
                       jnp.asarray(live))
        return tables, stats

    def warm_tables(self) -> List[Tuple[Any, ...]]:
        """All-trash tables, one set a view width, to compile the decode
        program on: every row is dead there (its writes go to the trash
        block, its tokens to nobody)."""
        ring = ()
        if self.rings is not None:
            ring = (jnp.full((self.slots, self.rings.cols), self.rings.trash,
                             jnp.int32), jnp.zeros((self.slots,), bool))
        return [(jnp.full((self.slots, width), self.alloc.trash, jnp.int32),)
                + ring for width in self.view_widths]

    def chunk_tables(self, slot: int, start: int, end: int, chunk: int
                     ) -> Tuple[np.ndarray, ...]:
        """The tables of ONE prefill chunk (positions ``[start, end)`` in a
        program of ``chunk``) of a held slot that prefills straight into
        the arenas: the row's own table up to the narrowest view width
        covering ``end``, the full-kind blocks the chunk writes (a row a
        position only: the summary kind's program finds its rows in the
        table), and with a ring kind the ring as the previous chunk left it
        and the ring blocks the chunk writes. Grants the chunk its blocks of both kinds first; the
        ring's older blocks go back before the new ones are granted, so the
        chunk writes only what the next reader (the next chunk, or decode)
        can still see."""
        res, bt, trash = self._res[slot], self.block_t, self.alloc.trash
        table = self._filling.get(slot)
        if table is None:
            table = self._filling[slot] = np.full((self.max_blocks,), trash,
                                                  np.int32)
        self._grant_into(table, res, end)
        held, first_block = self.blocks_for(end), start // bt
        view = next(w for w in self.view_widths if w >= held)
        write_full = np.full((chunk // bt,), trash, np.int32)
        if self.stride == 1:
            write_full[:held - first_block] = table[first_block:held]
        rings = self.rings
        if rings is None:
            return table[:view], write_full
        read_ring = rings.row(slot).copy()
        rings.advance(slot, end, end)
        write_ring = np.asarray(
            [rings.block_of(slot, first_block + j) for j in range(chunk // bt)],
            np.int32)
        if self.stride > 1:
            return table[:view], read_ring, write_ring
        return table[:view], write_full, read_ring, write_ring


class ContiguousKV:
    """:class:`SlotKV`'s verbs with nothing behind them, for the contiguous
    cache (``paged=False``, the tests' parity reference): every slot's
    ``max_seq`` rows are its own on the device for good, so nothing is
    reserved, granted or given back and a dispatch takes no table."""

    block_t = 0

    def reserve(self, tokens: int) -> KVReservation:
        return KVReservation(total=0)

    def bind(self, slots, reservations, prompt_lens, padded=0) -> Tuple[()]:
        return ()

    def dispatch_tables(self, active) -> Tuple[Tuple[()], Dict[str, int]]:
        return (), {}

    def warm_tables(self) -> List[Tuple[Any, ...]]:
        return []

    def _nothing(self, *args: Any) -> None:
        pass

    check = hold = release = advance = settle = _nothing
