"""Paged KV-cache block accounting for the continuous-batching engine.

ISSUE 12: the per-slot contiguous KV cache reserved worst-case
``max_seq`` rows per slot whether a request used 20 tokens or 2000. The
paged layout keeps ONE shared arena of fixed-size blocks per layer
(``[n_blocks + 1, block_t, heads, head_dim]`` — the last row is the trash
block) and a host-side per-slot block table mapping absolute positions to
arena rows. This module owns the host-side half: the free-list allocator
that reserves capacity at admission and grants physical blocks as cursors
advance, published as ``serving_kv_blocks_{free,used}`` gauges so arena
sizing is an observable capacity knob rather than a silent OOM.

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

The device-side correctness contract lives in
``kubeflow_tpu/ops/kv_cache.py`` (trash-block convention) and
``serving/continuous.py`` (retire ordering: table row → trash BEFORE
blocks return to the free list, so stale in-flight dispatches write to
trash, never into a re-granted block).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

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
    """
    total: int
    granted: List[int] = field(default_factory=list)


class KVBlockAllocator:
    """LIFO free-list allocator over ``n_blocks`` arena rows.

    Row ``n_blocks`` (the arena's last row — callers allocate
    ``n_blocks + 1`` rows) is the trash block and is never handed out;
    :attr:`trash` exposes its id for table initialization.
    """

    def __init__(self, n_blocks: int, block_t: int, *, engine_id: str = "0",
                 kind: str = ""):
        """``kind`` ("full" / "window") labels the gauges of an engine that
        keeps two kinds of cache side by side; an engine with one kind
        leaves it empty and publishes the gauges it always did."""
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
        free list. The caller MUST have redirected the slot's table row to
        trash before calling this (retire ordering invariant)."""
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

    def __init__(self, slots: int, window: int, block_t: int, lookahead: int,
                 *, engine_id: str = "0"):
        self.window, self.block_t = int(window), int(block_t)
        self.cols = -(-(self.window + max(int(lookahead), 1) - 1) // self.block_t) + 1
        # a whole ring for every slot: a slot can never hold more
        self.alloc = KVBlockAllocator(slots * self.cols, block_t,
                                      engine_id=engine_id, kind="window")
        self.trash = self.alloc.trash
        self.tables = np.full((slots, self.cols), self.trash, np.int32)
        self._res: Dict[int, KVReservation] = {}
        self._held: Dict[int, Dict[int, int]] = {}     # slot -> logical block -> id
        self._frontier = np.zeros((slots,), np.int64)  # positions a full kind would hold

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
        the blocks wholly behind ``cursor - window + 1`` and grant those up
        to the frontier. Returns the ids of the logical blocks
        ``[first kept .. last]`` now held, oldest first."""
        res, held = self._res[slot], self._held[slot]
        first = max(0, cursor - self.window + 1) // self.block_t
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
        """Retire: the row goes to trash, then the blocks return."""
        self.tables[slot, :] = self.trash
        res = self._res.pop(slot, None)
        self._held.pop(slot, None)
        self._frontier[slot] = 0
        if res is not None:
            self.alloc.release(res)

    def used(self) -> int:
        return self.alloc.used()

    def unreleased(self) -> int:
        """Blocks the window kind would hold had nothing been given back:
        one per ``block_t`` positions of every attached row's frontier."""
        return int(sum(-(-int(self._frontier[s]) // self.block_t) for s in self._res))
