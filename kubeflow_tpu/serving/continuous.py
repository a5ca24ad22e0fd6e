"""Continuous batching for KV-cache decode (VERDICT r3 #8).

The static batcher (serving/batching.py) coalesces whole requests: a batch
decodes in lockstep and every sequence pays for the LONGEST member's token
budget. For autoregressive serving the mechanism that matters is
slot-based admission — vLLM-style scheduling expressed the TPU way:

- ONE compiled decode step over a fixed ``slots``-row batch (static
  shapes, compiled once), every step produces one token per slot,
- the shared KV cache keeps a cursor PER ROW (models/gpt.py
  ``per_slot=True``), so rows are independent sequences at independent
  positions,
- new requests admit in WAVES: arrivals coalesce, each same-prompt-bucket
  group (chunked to at most ``min(slots, MAX_GROUP)`` rows) runs ONE
  batched prefill padded to that fixed size and ONE multi-row adopt
  splice — no host round trip on the admission path (first tokens are
  fetched lazily as pipelined events),
- finished slots (budget reached / EOS) free at event-processing time and
  the next queued request takes the row — no drain barrier, no padding to
  the longest request,
- chunk dispatches overlap (bounded ``pipeline`` depth) so the
  dispatch+fetch round trip hides behind decode compute.

Throughput model: mixed arrivals with budgets b_i on S slots cost
~max-ish(sum b_i / S) steps here vs sum-of-group-max for the static
batcher. e2e/serving_bench.py:bench_continuous measures both on the same
workload.
"""

from __future__ import annotations

import collections
import functools
import math
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.metrics import METRICS
from ..runtime.tracing import TRACER, Span
from ..tpu import profiling
from .errors import (DeadlineExceeded, EngineClosed, FleetSaturated,
                     RequestCancelled)
from .family import family_for
from .paged import ContiguousKV, KVReservation, SlotKV

# the programs a server compiles before it builds its engine are counted too
profiling.watch_compiles()

#: admission priority classes; batch is shed first under saturation
PRIORITIES = ("interactive", "batch")

#: prompt-length buckets — one prefill compilation each (static shapes)
PREFILL_BUCKETS = (16, 32, 64, 128, 256)

#: SLO histogram ladders (docs/OBSERVABILITY.md). The registry default
#: (1ms–30s) cannot resolve ms-scale inter-token latency, and TTFT needs
#: headroom past 30s for cold-compile admissions.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0, 30.0, 60.0)
ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
               0.5, 1.0)
QUEUE_WAIT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0,
                      60.0)
PREFILL_BUCKETS_S = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                     10.0)
DECODE_CHUNK_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                        0.5, 1.0, 2.5)
#: KV handoff blob sizes span ~KBs (tiny configs) to ~100s of MB (long
#: prompts on the base config) — a power-of-8 ladder covers both
HANDOFF_BYTES_BUCKETS = (1024.0, 8192.0, 65536.0, 524288.0, 4194304.0,
                         33554432.0, 268435456.0)

#: ceiling on one batched prefill's rows: every admission group is padded
#: to ``min(slots, MAX_GROUP)`` (ONE prefill program + ONE reusable zero
#: template per prompt bucket; larger waves are chunked). Padding a
#: 1-request group to 8 rows costs only hidden prefill compute — the
#: round-5 cost model says dispatch round trips, not prompt flops, bound
#: admission.
MAX_GROUP = 8

#: drain-queue sentinel (distinct from the ``None`` shutdown sentinel):
#: the worker stops admitting, finishes in-flight slots, then parks the
#: unserved pendings for handoff instead of failing them
_DRAIN = object()
#: "no item in hand" while draining the queue (``None`` is the shutdown sentinel)
_NOTHING = object()


def _bucket_for(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest prefill bucket")


def _block_tile(max_seq: int, requested: int = 16) -> int:
    """Arena tile (``block_t``) for the paged KV layout: the largest value
    not above ``requested`` that divides both ``max_seq`` (so the gathered
    [S, max_blocks*block_t] view is shape-identical to the contiguous
    cache — the bit-parity contract) and the smallest prefill bucket (so
    every bucket splice is a whole number of blocks)."""
    base = math.gcd(int(max_seq), PREFILL_BUCKETS[0])
    return next(b for b in range(min(int(requested), base), 0, -1)
                if base % b == 0)


def effective_prefill_chunk(requested: Optional[int], max_seq: int,
                            block_t: int = 1) -> int:
    """Resolve the chunked-prefill chunk size an engine will actually use:
    the largest value not above ``requested`` that divides ``max_seq``
    (chunk starts must never clamp inside the scalar-cursor prefill cache)
    and is a whole number of KV blocks. ``requested`` None defaults to the
    largest prefill bucket; 0/negative disables chunking (returns 0).
    ``GenerativeModel`` calls this too, so routing and engine agree."""
    if requested is None:
        requested = PREFILL_BUCKETS[-1]
    requested = min(int(requested), int(max_seq))
    if requested <= 0:
        return 0
    step = max(int(block_t), 1)
    for c in range(requested, 0, -1):
        if max_seq % c == 0 and c % step == 0:
            return c
    return 0


@dataclass(eq=False)  # identity equality: field eq would compare ndarrays
class _Request:
    prompt: np.ndarray  # [prompt_len] int32
    max_new_tokens: int
    done: threading.Event = field(default_factory=threading.Event)
    tokens: List[int] = field(default_factory=list)
    #: beside each token, where the family's step hands one out: the forward
    #: pass of its block that revealed it (0: it was the prompt's)
    reveal_passes: List[int] = field(default_factory=list)
    error: Optional[BaseException] = None
    eos_id: Optional[int] = None
    temperature: float = 0.0  # 0 = greedy; >0 samples with a per-slot key
    done_at: Optional[float] = None  # perf_counter at retirement (latency acct)
    # overload-protection state (ISSUE 9):
    deadline: Optional[float] = None  # absolute time.monotonic(); None = no deadline
    priority: str = "interactive"     # "interactive" | "batch"
    cancel_requested: bool = False    # client abandoned; worker reaps the slot
    #: how the request ended: "ok" (budget/EOS), "deadline" (expired
    #: mid-decode, partial tokens), "cancelled" (abandoned mid-decode),
    #: "error" (failed) — the fleet's breaker feedback keys off this
    finish_reason: Optional[str] = None
    #: fired exactly once when ``done`` is set, from whichever thread
    #: finished the request — the fleet hangs replica-outcome accounting
    #: (circuit breakers) here
    on_done: Optional[Callable[["_Request"], None]] = None
    # observability (None on internal requests, e.g. prewarm's dummies):
    # one span covers submit()→_retire(), crossing the caller thread into
    # the engine worker — hence start_span/end_span, not the contextmanager
    span: Optional[Span] = None
    submit_at: Optional[float] = None       # perf_counter at enqueue
    first_token_at: Optional[float] = None  # perf_counter at first token
    last_token_at: Optional[float] = None   # perf_counter at latest token
    #: multiplexing id (ISSUE 18) — which served model this request targets
    model_id: str = ""
    #: the request's exported KV wire blob, once a prefill replica has
    #: shipped it — a decode-pool drain hands the request back with this
    #: set so the fleet can re-import it on a surviving decode replica
    #: instead of re-running prefill
    kv_blob: Optional[bytes] = None

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("request not finished")
        if self.error is not None:
            raise self.error
        return self.tokens

    def remaining(self, default: Optional[float] = None) -> Optional[float]:
        """Seconds until the deadline (negative once past); ``default``
        when no deadline is set."""
        if self.deadline is None:
            return default
        return self.deadline - time.monotonic()

    def expired(self) -> bool:
        return self.deadline is not None and time.monotonic() >= self.deadline

    def cancel(self) -> bool:
        """Abandon the request (client disconnect / explicit cancel). A
        queued request fails fast with :class:`RequestCancelled`; an
        in-flight one frees its slot within ~one decode chunk and
        completes with the partial tokens. False if already finished."""
        if self.done.is_set():
            return False
        self.cancel_requested = True
        return True

    def _notify(self) -> None:
        cb, self.on_done = self.on_done, None
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass


def _ev(req: _Request, name: str, **attrs: Any) -> None:
    if req.span is not None:
        req.span.add_event(name, **attrs)


def _compile_counts() -> Dict[str, float]:
    """``xla_compiles_total`` by outcome (``tpu.profiling.watch_compiles``)."""
    return {outcome: METRICS.value("xla_compiles_total", outcome=outcome)
            for outcome in ("compiled", "loaded")}


def _trace_id(req: _Request) -> Optional[str]:
    return req.span.trace_id if req.span is not None else None


def _fail(req: _Request, error: BaseException) -> None:
    """Single failure path: error the future AND close the span — every
    branch that drops a request (bad bucket, prefill/adopt failure,
    shutdown) must leave its trace ERROR-terminated, not dangling."""
    req.error = error
    if req.finish_reason is None:
        req.finish_reason = "error"
    if req.span is not None:
        TRACER.end_span(req.span, error=error)
        req.span = None
    req.done.set()
    req._notify()


@dataclass(eq=False)
class _ChunkedPrefill:
    """One long prompt mid-chunked-prefill (ISSUE 12): it owns a slot and
    a KV reservation from the first chunk, prefills into a private
    [1, max_seq] scalar-cursor cache one fixed-size chunk per engine
    iteration — decode chunks keep dispatching in between, which is the
    whole point — and adopts into the shared cache when the last chunk
    lands. A family that prefills straight into the arenas has no private
    cache (``cache`` None; the row's table while it fills is the KV
    owner's) and carries its expert counters so far in ``stats``."""
    req: _Request
    slot: int
    cache: Any
    key: Any
    res: KVReservation
    pos: int = 0                       # prompt tokens prefilled so far
    stats: Any = None


@dataclass(eq=False)
class _Import:
    """One KV-wire import awaiting a decode slot (ISSUE 18): the request
    was prefilled on a PREFILL-pool replica; its KV blocks arrived here
    already computed (and, int8, already quantized). Admission reserves
    arena blocks like any other request — wire imports get no back-pressure
    exemption — then scatters the blocks in one jitted call."""
    req: _Request
    manifest: Dict[str, Any]
    arrays: Dict[str, np.ndarray]


class ContinuousBatcher:
    """Slot-based decode engine over one per-slot KV cache.

    Usage:
        eng = ContinuousBatcher(cfg, params, slots=8)
        fut = eng.submit([1, 2, 3], max_new_tokens=32)
        tokens = fut.result(timeout=60)
        eng.close()

    ``chunk`` = decode steps per dispatch: each engine iteration runs a
    jitted ``lax.scan`` of that many single-token steps and fetches the
    [slots, chunk] token block once. chunk=1 is purest continuous batching
    but pays one dispatch + host round-trip PER TOKEN. Chunking amortizes
    dispatch like the training benches amortize scan overhead; admission/
    retirement happen at chunk boundaries (a slot finishing mid-chunk
    discards its tail tokens — the cache stays correct because adoption
    resets the row cursor).

    ``pipeline`` = chunk dispatches kept in flight. A dispatch+fetch
    round trip has a fixed cost beside the marginal decode compute per
    token, and a deep dispatch queue degrades. So the engine keeps a
    bounded event pipeline: chunks are dispatched asynchronously (token
    blocks fetched via ``copy_to_host_async``), and retirement/admission
    decisions lag ``pipeline`` chunks behind the dispatch frontier, hiding
    the round trip behind compute. Lagged decisions are safe because
    inactive rows cost nothing (the batch shape is fixed; a retired row's tail tokens are discarded
    against the dispatch-time snapshot) and adoptions join the donated
    cache chain in dispatch order.
    """

    def __init__(self, cfg: Any, params: Any, slots: int = 8,
                 chunk: int = 16, pipeline: int = 3,
                 engine_id: str = "0",
                 max_pending: int = 0,
                 interactive_reserve: float = 0.25,
                 paged: bool = True,
                 kv_blocks: Optional[int] = None,
                 kv_block_t: int = 16,
                 prefill_chunk: Optional[int] = None,
                 spec_draft: Optional[Tuple[Any, Any]] = None,
                 spec_k: int = 4,
                 kv_dtype: str = "bf16",
                 role: str = "unified",
                 model_id: str = "",
                 handoff_sink: Optional[Callable[["_Request", bytes], None]] = None):
        """``cfg`` names the model family by its type (``serving/family.py``):
        the engine builds no model itself, and it keeps no account of a
        slot's KV: ``self.kv`` (``paged.SlotKV``) owns the block table, the
        reservations, the cursor bounds and the retire order. A family may
        keep a second kind of paged cache beside the block table, a ring of
        blocks a slot (``paged.WindowRings``: given back as the cursor
        leaves them behind; ``paged.AlignedWindows``: a whole window at its
        end), sized from ``slots``, the window, ``kv_block_t`` and
        ``chunk``: two kinds of cache add no knob.

        New ISSUE-12 knobs (defaults keep every pre-existing behavior):

        ``paged``: shared block-arena KV layout with a per-slot block table
        (default). ``paged=False`` keeps the contiguous per-slot cache as
        the parity ground truth — the same pattern as
        ``ChipLedger(indexed=True)``. A paged decode dispatch reads the
        arena through the block table's first columns only, up to the
        longest granted row (``paged.view_blocks``; the stat
        ``view_blocks`` of ``serving.engine.dispatch``, the gauge
        ``serving_decode_view_blocks``): a step costs what the longest live
        sequence needs, not ``max_seq`` a slot.

        ``kv_blocks``: allocatable arena blocks (None = full capacity
        parity, ``slots * ceil(max_seq / block_t)`` — no admission
        back-pressure beyond the contiguous layout's). Smaller arenas trade
        HBM for ``KVBlocksExhausted`` back-pressure under long-prompt load;
        watch ``serving_kv_blocks_{free,used}``.

        ``kv_block_t``: requested arena tile; auto-shrunk so it divides
        ``max_seq`` and the smallest prefill bucket (bit-parity contract).

        ``prefill_chunk``: prompts longer than this prefill in fixed-size
        chunks interleaved with decode dispatches (None = the largest
        prefill bucket, which also extends the engine's servable prompt
        range from that bucket up to ``max_seq - budget``; 0 disables —
        long prompts then fail fast at admission).

        ``spec_draft``: ``(draft_cfg, draft_params)`` enables speculative
        decoding — the draft greedily proposes ``spec_k - 1`` tokens per
        round, the target verifies all positions in ONE batched forward,
        and the accepted prefix commits with cursor rollback on both
        caches. Greedy requests stay bit-identical to plain decode;
        sampled slots accept exactly one token per round, drawn from the
        verify logits.

        New ISSUE-18 knobs:

        ``kv_dtype``: arena storage precision — ``"bf16"`` (default,
        bit-parity ground truth) or ``"int8"`` (symmetric per-(row, head)
        quantized arena + f32 scale arena: 2x KV positions per HBM byte;
        greedy decode stays within the tested logit tolerance). int8
        requires the paged layout.

        ``role``: ``"unified"`` (default — prefill and decode in one
        engine), ``"prefill"`` (runs prefill ONLY: every admitted request
        is prefilled, exported to the KV wire format, and handed to
        ``handoff_sink(req, blob)`` — ownership transfers; the sink routes
        it to a decode replica), or ``"decode"`` (additionally accepts
        :meth:`submit_handoff` imports whose KV arrives pre-filled over
        the wire).

        ``model_id``: the served model's multiplexing id — stamped into
        exported KV manifests so a decode replica can refuse a wire blob
        from the wrong model.
        """
        built_from = time.time_ns()
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.kv_dtype = str(kv_dtype)
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype {self.kv_dtype!r}: expected bf16|int8")
        if self.kv_dtype == "int8" and not paged:
            raise ValueError("kv_dtype='int8' requires paged=True")
        self.role = str(role)
        if self.role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role {self.role!r}: expected unified|prefill|decode")
        if self.role != "unified" and not paged:
            raise ValueError(
                "prefill/decode roles require paged=True (the KV wire "
                "format is block-shaped)")
        self.model_id = str(model_id)
        self.handoff_sink = handoff_sink
        # engine id -> the ``replica`` label on this engine's gauges: N
        # engines sharing one process registry (the fleet) must not clobber
        # each other's queue_depth / slot_occupancy series
        self.engine_id = str(engine_id)
        self.chunk = max(1, int(chunk))
        self.pipeline = max(1, int(pipeline))
        # admission-queue cap (0 = unbounded): when the queue is full,
        # batch requests shed at (1 - interactive_reserve) * max_pending
        # while interactive keeps the full depth — a batch flood cannot
        # starve interactive admission (ISSUE 9)
        self.max_pending = max(0, int(max_pending))
        self.interactive_reserve = min(max(float(interactive_reserve), 0.0), 1.0)
        #: chaos hooks (runtime/chaos.py slow_replica /
        #: crash_replica_mid_decode): added latency per engine iteration,
        #: and a one-shot poison that fails the next iteration
        self.step_delay_s = 0.0
        self.fail_next_step = False
        # fixed admission-group pad: one prefill program + one zero
        # template per prompt bucket; waves larger than this are chunked
        self._group_pad = min(slots, MAX_GROUP)
        # -- paged KV layout (ISSUE 12) ------------------------------------
        if paged:
            self.kv_block_t = _block_tile(cfg.max_seq, kv_block_t)
        else:
            self.kv_block_t = 0
        # kv_blocks 0: the family's default, a whole row of the table a slot
        self.family = family_for(
            cfg, slots=slots, paged=bool(paged), kv_blocks=int(kv_blocks or 0) if paged else 0,
            kv_block_t=self.kv_block_t, kv_dtype=self.kv_dtype)
        # the one owner of every slot's KV on the host. A family may keep a
        # second kind of cache, a ring a slot beside the block table (a
        # dispatch moves a cursor by up to ``chunk`` positions), and a row
        # of its table may stand for more than one position, and a step
        # may work on a block of positions past the cursor.
        self.kv = (SlotKV(slots, cfg.max_seq, self.kv_block_t, self.family.kv_blocks,
                          engine_id=self.engine_id,
                          rings=self.family.rings(self.chunk, self.engine_id),
                          stride=self.family.kv_stride, ahead=self.family.kv_ahead)
                   if paged else ContiguousKV())
        # every view width's decode program is compiled once, at the first
        # prewarm: "no" -> "asked" (prewarm) -> "done" (the engine thread,
        # at a turn that finds no slot active; a contiguous cache has no
        # width to warm). A prefill specialist ships its requests before
        # they decode.
        self._view_warmup = "no" if self.role != "prefill" else "done"
        # -- chunked prefill (ISSUE 12) ------------------------------------
        self.prefill_chunk = effective_prefill_chunk(
            prefill_chunk, cfg.max_seq, self.kv_block_t or 1)
        self._chunked: Optional[_ChunkedPrefill] = None
        self._chunk_prefill_fn: Optional[Any] = None
        self._draft_full_prefill_fn: Optional[Any] = None
        # -- speculative decoding (ISSUE 12) -------------------------------
        self.spec_k = 0
        if spec_draft is not None:
            draft_cfg, draft_params = spec_draft
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("spec draft must share the target's vocab")
            if draft_cfg.max_seq < cfg.max_seq:
                raise ValueError("spec draft max_seq must cover the target's")
            self.spec_k = max(2, int(spec_k))
            self._draft_cfg = draft_cfg
            self._draft_params = draft_params
            # the draft stays contiguous: it is small by construction, so
            # the paged arena's memory win does not apply to it
            self._draft_family = family_for(draft_cfg, slots=slots)
        if self.family.prefills_in_arena:
            # a family that prefills into the arenas has no private cache
            # to adopt, to ship or to verify drafts against
            if self.spec_k or self.role != "unified":
                raise ValueError(
                    f"{type(cfg).__name__}: speculation and the prefill / "
                    "decode roles are not built for this model family")
            if not self.prefill_chunk:
                raise ValueError(
                    f"{type(cfg).__name__} prefills in chunks: prefill_chunk "
                    "must not be 0")
        # the most positions one decode dispatch moves a slot's cursor, and
        # the decode dispatches whose events are still to be processed
        self._moves = self.spec_k or self.family.cursor_moves(self.chunk)
        self._decodes_in_flight = 0
        self.cache = self.family.fresh_cache()
        if self.spec_k:
            self.draft_cache = self._draft_family.fresh_cache()
        self.last_tok = jnp.zeros((slots,), jnp.int32)
        # per-slot sampling state: temperature 0 = greedy; each admission
        # folds a fresh counter into the base key so sampled requests draw
        # independent streams (same recipe as GenerativeModel's rng)
        self.temps = jnp.zeros((slots,), jnp.float32)
        self._base_rng = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "little"))
        self._rng_counter = 0
        # split (not fold_in) for the initial keys so they can never collide
        # with the admission counter's fold_in stream
        self.rngs = jax.random.split(self._base_rng, slots)
        # queue items are WAVES (lists of requests enqueued atomically) so a
        # caller can hand the worker a group it should admit together;
        # submit() enqueues singleton waves. None is the shutdown sentinel.
        self._queue: "queue.Queue[Optional[List[_Request]]]" = queue.Queue()
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._active: Dict[int, _Request] = {}
        self._free = list(range(slots))
        self._lock = threading.Lock()
        self._closed = False
        self._draining = False
        #: requests drain() could not serve — handed off to the fleet router
        self._handoff: List[_Request] = []
        #: wire-format KV imports awaiting a slot (decode role, ISSUE 18)
        self._imports: "collections.deque[_Import]" = collections.deque()
        self._step_fn = self.family.build_step(self.chunk)
        batched = not self.family.prefills_in_arena
        self._adopt_fn = self.family.build_adopt() if batched else None
        self._import_fn = (self.family.build_import()
                           if batched and paged else None)
        self._spec_fn = self._build_spec_step() if self.spec_k else None
        self._draft_adopt_fn = (self._draft_family.build_draft_adopt()
                                if self.spec_k else None)
        self._activate_fn = None if batched else self.family.build_activate()
        self._prefill_fns: Dict[Tuple[int, int, bool], Any] = {}
        # reusable zero prefill-cache per group bucket: prefill does NOT
        # donate its cache input, so one template serves every admission —
        # without it each wave re-allocates 2*n_layers zero buffers on the
        # device
        self._zero_small: Dict[Tuple[int, bool], Any] = {}
        self._worker = threading.Thread(target=self._loop, name="continuous-batcher",
                                        daemon=True)
        self._worker.start()
        # arenas, tables and the family's program builders (nothing compiles
        # before a program's first call): a replica's start, before prewarm
        TRACER.emit_span("serving.engine.build", built_from, time.time_ns(),
                         replica=self.engine_id, slots=slots)

    # -- compiled pieces -----------------------------------------------------
    # (the family's: serving/family.py builds every program that knows what
    # a cache leaf is; the engine keeps the ones that only sequence models)
    def _launch(self, program: str, fn: Callable[..., Any], *args: Any,
                first: Optional[_Request] = None) -> Any:
        """Every call of a device program on the engine thread: one
        ``serving.engine.launch`` region, the innermost of its phase, so an
        idle gap of the device is put down to the launch of a named
        ``program``; a call that compiled or loaded an executable says so
        itself (``compiles``). The call returns when the program is
        enqueued, which is late where the device's queue is full. ``first``:
        the first request of a batched prefill, whose trace the call's wall
        time is filed under in ``serving_prefill_seconds``."""
        seen = profiling.compiles_seen()
        with profiling.annotate("serving.engine.launch", program=program) as region:
            t0 = time.perf_counter()
            out = fn(*args)
            if first is not None:
                METRICS.histogram(
                    "serving_prefill_seconds", buckets=PREFILL_BUCKETS_S
                ).observe(time.perf_counter() - t0, trace_id=_trace_id(first))
            compiles = profiling.compiles_seen() - seen
            if compiles:
                region.set_metadata(compiles=compiles)
        return out

    def _build_spec_step(self):
        """One speculative round: the draft model greedily proposes
        ``spec_k`` tokens (``spec_k - 1`` of them verifiable), the target
        verifies all positions in ONE seg_len=spec_k forward, and both
        caches roll their cursors back to the accepted frontier.

        Accept-prefix semantics (greedy slots): emitted tokens are
        ``t_1 .. t_m`` with ``m = 1 + (leading draft/target matches)`` —
        exactly the tokens plain greedy decode would emit, because each
        ``t_j`` is conditioned only on accepted history. Position ``C+j``
        of both caches holds the KV of a matched (= accepted) token for
        every ``j < m``, so rollback to ``C + m`` leaves both caches
        bit-identical to a plain decode that emitted the same tokens; the
        stale KV above the frontier is overwritten before it is ever
        unmasked. Sampled slots accept exactly one token per round, drawn
        from the verify logits at position 0 (one key split per round).
        """
        model, draft_model = self.family.model, self._draft_family.model
        k = self.spec_k
        _rollback = self.family.rollback

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4, 6))
        def spec(params, dparams, cache, dcache, tok, temps, rngs, *tables):
            def draft_one(carry, _):
                dcache, tok = carry
                logits, updated = draft_model.apply(
                    {"params": dparams, "cache": dcache}, tok[:, None],
                    mutable=["cache"])
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (updated["cache"], nxt), nxt

            # k draft steps: writes the draft KV for tok and d_1..d_{k-1}
            # (so a fully accepted round leaves the draft cache complete
            # after rollback); d_k itself is never verified
            (dcache, _), drafts = jax.lax.scan(
                draft_one, (dcache, tok), None, length=k)
            drafts = jnp.moveaxis(drafts, 0, 1)                  # [S, k]
            seg = jnp.concatenate([tok[:, None], drafts[:, :k - 1]], axis=1)
            kwargs = {"block_tables": tables[0]} if tables else {}
            logits, updated = model.apply(
                {"params": params, "cache": cache}, seg,
                mutable=["cache"], **kwargs)
            cache = updated["cache"]
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k]
                pairs = jax.vmap(jax.random.split)(rngs)
                rngs, keys = pairs[:, 0], pairs[:, 1]
                sampled = jax.vmap(
                    lambda k_, l, t: jax.random.categorical(
                        k_, l / jnp.maximum(t, 1e-6))
                )(keys, logits[:, 0], temps).astype(jnp.int32)
            match = (drafts[:, :k - 1] == greedy[:, :k - 1]).astype(jnp.int32)
            m_greedy = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            m = jnp.where(temps > 0.0, 1, m_greedy).astype(jnp.int32)  # [S]
            toks = jnp.where(temps[:, None] > 0.0,
                             jnp.concatenate([sampled[:, None], greedy[:, 1:]],
                                             axis=1),
                             greedy)                             # [S, k]
            cache = _rollback(cache, k - m)
            dcache = _rollback(dcache, k - m)
            last = jnp.take_along_axis(toks, (m - 1)[:, None], axis=1)[:, 0]
            return cache, dcache, last, rngs, toks, m

        return spec

    def _prefill_group(self, prompts: Sequence[np.ndarray],
                       temperatures: Sequence[float], keys,
                       draft: bool = False,
                       first: Optional[_Request] = None) -> Tuple[Any, Any]:
        """ONE batched prefill for a same-length-bucket admission group:
        [n_pad, bucket] prompt forward on a reused zero [n_pad, max_seq]
        cache (shared cursor 0 — every row starts at position 0), padded
        to the engine's single fixed group size so every group reuses one
        compilation and one template. Returns (small cache, first token
        per row). Round 4 measured ~141 ms of mostly fixed dispatch cost
        PER single-prompt admission; batching amortizes that over up to
        ``n_pad`` arrivals."""
        n = len(prompts)
        bucket = _bucket_for(max(len(p) for p in prompts))
        n_pad = self._group_pad
        if n > n_pad:
            raise ValueError(f"admission group of {n} exceeds pad {n_pad}")
        if (bucket, n_pad, draft) not in self._prefill_fns:
            model = (self._draft_family if draft else self.family).prefill_model

            @jax.jit
            def prefill(params, cache, ids, true_lens, temperatures, keys):
                logits, updated = model.apply(
                    {"params": params, "cache": cache}, ids, mutable=["cache"]
                )
                # each row's first generated token comes from ITS true last
                # prompt position, not the padded bucket end
                lg = jnp.take_along_axis(
                    logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                sampled = jax.vmap(
                    lambda k_, l, t: jax.random.categorical(
                        k_, l / jnp.maximum(t, 1e-6))
                )(keys, lg, temperatures).astype(jnp.int32)
                first = jnp.where(temperatures > 0.0, sampled, greedy)
                return updated["cache"], first

            self._prefill_fns[(bucket, n_pad, draft)] = prefill
        if (n_pad, draft) not in self._zero_small:
            self._zero_small[(n_pad, draft)] = (
                self._draft_family if draft else self.family).prefill_cache(n_pad)
        small = self._zero_small[(n_pad, draft)]
        ids = np.zeros((n_pad, bucket), np.int32)
        true_lens = np.ones((n_pad,), np.int32)
        temps = np.zeros((n_pad,), np.float32)
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = p
            true_lens[i] = len(p)
            temps[i] = temperatures[i]
        if keys.shape[0] != n_pad:  # pad the key rows (unused rows ignored)
            keys = jnp.concatenate(
                [keys, jnp.zeros((n_pad - n, 2), keys.dtype)], axis=0)
        return self._launch(
            "draft_prefill" if draft else "prefill",
            self._prefill_fns[(bucket, n_pad, draft)],
            self._draft_params if draft else self.params, small,
            jnp.asarray(ids), jnp.asarray(true_lens),
            jnp.asarray(temps), keys, first=first)

    # -- public API ----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               eos_id: Optional[int] = None,
               temperature: float = 0.0,
               traceparent: Optional[str] = None,
               deadline: Optional[float] = None,
               priority: str = "interactive",
               on_done: Optional[Callable[[_Request], None]] = None) -> _Request:
        """``traceparent`` (W3C header value) parents the request's span to
        the caller's trace — the HTTP predict handler passes its own so a
        scraped trace shows the handler as root over submit→retire.

        ``deadline`` is an ABSOLUTE ``time.monotonic()`` instant: a request
        whose deadline passes while queued fails fast with
        :class:`DeadlineExceeded` (never occupies a slot); one that expires
        mid-decode frees its slot within ~one decode chunk and completes
        with the partial tokens. An already-expired deadline fails the
        returned future immediately — no exception from submit itself, so
        the fleet's retry path can't mistake it for a dead replica."""
        if priority not in PRIORITIES:
            raise ValueError(f"priority {priority!r}; expected one of {PRIORITIES}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens > self.cfg.max_seq:
            raise ValueError("prompt + budget exceeds max_seq")
        self.kv.check(len(prompt) + max_new_tokens)
        req = _Request(prompt, max_new_tokens, eos_id=eos_id,
                       temperature=float(temperature),
                       deadline=deadline, priority=priority, on_done=on_done,
                       model_id=self.model_id)
        req.span = TRACER.start_span(
            "serving.request", traceparent=traceparent,
            **{"prompt_tokens": int(len(prompt)),
               "max_new_tokens": int(max_new_tokens),
               "priority": priority,
               # federated queries isolate one fleet replica's decode path
               # by this label (the /debug/traces?service= counterpart)
               "replica": self.engine_id})
        req.submit_at = time.perf_counter()
        _ev(req, "enqueued")
        METRICS.counter("serving_tokens_in_total").inc(len(prompt))
        if req.expired():  # dead on arrival: shed before it costs anything
            METRICS.counter("serving_deadline_expired_total",
                            stage="queued").inc()
            _ev(req, "deadline_expired", stage="queued")
            req.finish_reason = "deadline"
            # pre-admission expiry says nothing about THIS replica's health:
            # suppress the fleet's breaker callback
            req.on_done = None
            _fail(req, DeadlineExceeded("deadline already expired at submit"))
            return req
        # closed-check and enqueue under one lock: a put racing close()
        # could otherwise land AFTER the shutdown sentinel and hang its
        # caller forever (the worker stops at the sentinel)
        with self._lock:
            if self._closed:
                _fail(req, EngineClosed("batcher closed"))
                raise EngineClosed("batcher closed")
            self._queue.put([req])
        return req

    def submit_handoff(self, req: _Request, blob: bytes) -> _Request:
        """Accept a request prefilled ELSEWHERE (decode role, ISSUE 18):
        ``blob`` is the KV wire export from a prefill-pool replica. The
        manifest and per-layer crc32s are verified here, synchronously —
        a corrupt or mismatched blob must fail on the caller's thread
        (where the fleet can still retry another replica), never poison
        the decode loop. The SAME request object continues: its future,
        span, and deadline all carry over, so TTFT measures the true
        submit→first-token path across both replicas."""
        if self.role == "prefill":
            raise ValueError("prefill-role engines cannot import KV")
        if self._import_fn is None:
            raise ValueError("KV import requires the paged arena layout "
                             "of a family that adopts prefilled KV")
        from .kv_wire import unpack_kv

        manifest, arrays = unpack_kv(blob)
        if manifest.get("kv_dtype") != self.kv_dtype:
            raise ValueError(
                f"wire kv_dtype {manifest.get('kv_dtype')!r} != engine "
                f"{self.kv_dtype!r}")
        if int(manifest.get("block_t", 0)) != self.kv_block_t:
            raise ValueError(
                f"wire block_t {manifest.get('block_t')} != engine "
                f"{self.kv_block_t}")
        if manifest.get("model_id", "") != self.model_id:
            raise ValueError(
                f"wire model {manifest.get('model_id')!r} != replica model "
                f"{self.model_id!r}")
        if int(manifest.get("prompt_len", -1)) != len(req.prompt):
            raise ValueError("wire prompt_len disagrees with the request")
        self.kv.check(len(req.prompt) + req.max_new_tokens)
        req.kv_blob = blob
        imp = _Import(req=req, manifest=manifest, arrays=arrays)
        with self._lock:
            if self._closed:
                raise EngineClosed("batcher closed")
            self._queue.put(imp)
        return req

    def cancel_requests(self, n: int = 1) -> int:
        """Abandon up to ``n`` in-flight or queued requests (the chaos
        harness's client-disconnect simulation; also the ops hook for
        evicting stuck work). Returns how many were marked — the worker
        reaps each within ~one decode chunk."""
        marked = 0
        for _ in range(3):
            try:
                reqs = list(self._active.values()) + list(self._pending)
                break
            except RuntimeError:
                continue  # worker resized a container mid-copy; retry
        else:
            return 0
        for req in reqs:
            if marked >= n:
                break
            if req.cancel():
                marked += 1
        return marked

    def prewarm(self, prompt_len: int,
                group_sizes: Optional[Sequence[int]] = None,
                timeout: float = 600.0) -> None:
        """Compile the engine's programs outside any latency-sensitive
        window: for each admission-group size, a wave of dummy requests is
        pushed as ONE queue item so the worker admits them together —
        exercising the (prompt-bucket, group-bucket) prefill, the exact-n
        adopt, and (for the largest wave) the chunked decode step, all
        through the production path. A paged engine's first prewarm also
        runs the decode program once at every view width (``SlotKV.warm_tables``):
        which width a dispatch takes depends on the longest live sequence,
        so no wave of dummies would meet them all. Compilations land in the
        persistent JAX cache when one is configured. ``timeout`` becomes each dummy
        request's deadline, so a wedged compile surfaces as
        :class:`DeadlineExceeded` instead of an 1800 s magic wait. Each call
        is one ``serving.engine.prewarm`` span that says how many
        executables the process ``compiled`` and how many it ``loaded`` from
        the persistent cache meanwhile."""
        deadline = time.monotonic() + timeout
        if self._view_warmup == "no":
            # before the first wave is enqueued: the engine thread compiles
            # the widths at the turn that takes the wave, ahead of admitting it
            self._view_warmup = "asked"
        # default: EVERY group size 1.._group_pad — the adopt program is
        # traced per exact group size (admission chunks larger waves to
        # _group_pad), so a size first seen mid-run would compile inside
        # somebody's latency window
        sizes = sorted({min(s, self._group_pad) for s in
                        (group_sizes if group_sizes is not None
                         else range(1, self._group_pad + 1))})
        before = _compile_counts()
        with TRACER.span("serving.engine.prewarm", replica=self.engine_id,
                         prompt_len=int(prompt_len), group_sizes=sizes) as span:
            for idx, n in enumerate(sizes):
                # waves run SEQUENTIALLY (each fully retired before the next
                # is enqueued) so the worker sees exactly one n-sized
                # admission — concurrent waves would coalesce in the pending
                # queue
                budget = self.chunk + 1 if idx == len(sizes) - 1 else 1
                wave = [_Request(np.zeros((prompt_len,), np.int32), budget,
                                 deadline=deadline)
                        for _ in range(n)]
                with self._lock:
                    if self._closed:
                        raise EngineClosed("batcher closed")
                    self._queue.put(wave)
                for req in wave:
                    # the wait derives from the request's own deadline (plus
                    # a grace period for the worker to reap+fail it) — the
                    # worker raises DeadlineExceeded through result() at
                    # expiry
                    req.result(timeout=max(0.0, deadline - time.monotonic()) + 5.0)
            for outcome, now in _compile_counts().items():
                span.set(outcome, int(now - before[outcome]))

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._queue.put(None)
        self._worker.join(timeout=30)

    def drain(self, timeout: float = 600.0) -> List[_Request]:
        """Graceful shutdown, distinct from ``close()``: stop admission,
        let the in-flight slots run to completion, then return the
        unserved requests (queued waves + pending) with their futures
        still open so a fleet can re-submit them to a surviving replica.
        ``close()`` after a drain is a no-op; submit() raises once the
        drain begins. Idempotent — a second call returns the same
        handoff list."""
        with self._lock:
            already = self._closed
            self._closed = True
            if not already:
                self._queue.put(_DRAIN)
        self._worker.join(timeout=timeout)
        return list(self._handoff)

    # -- engine loop ---------------------------------------------------------
    def _admit_wave(self, reqs: List[_Request]) -> List[Tuple[str, Any, Any]]:
        """One admission wave as one ``serving.engine.admit`` region:
        ``requests`` offered, and the largest prompt ``bucket`` it prefilled
        in a batch (0: every request went the chunked way or back)."""
        with profiling.annotate("serving.engine.admit",
                                requests=len(reqs)) as region:
            events, buckets = self._admit_groups(reqs)
            region.set_metadata(bucket=max(buckets, default=0))
        return events

    def _admit_groups(self, reqs: List[_Request]
                      ) -> Tuple[List[Tuple[str, Any, Any]], List[int]]:
        """Admit up to ``len(self._free)`` requests together: one batched
        prefill + one adopt per same-prompt-bucket group instead of the
        round-4 per-request dispatch chain (~141 ms each). Fully async —
        the first tokens stay on device (the adopt consumes them there) and
        are fetched lazily via the returned ``('first', toks, pairs)``
        events, so an admission adds NO host round trip to the dispatch
        chain."""
        events: List[Tuple[str, Any, Any]] = []
        by_bucket: Dict[int, List[Tuple[_Request, Any]]] = {}
        back: List[_Request] = []  # re-queued (chunked busy / arena full)
        for req in reqs:
            # fresh sampling key per admission (distinct stream per request)
            self._rng_counter += 1
            key = jax.random.fold_in(self._base_rng, self._rng_counter)
            if self.prefill_chunk and (len(req.prompt) > self.prefill_chunk
                                       or self.family.prefills_in_arena):
                # long prompt (or a family that prefills every prompt in
                # chunks) → chunked prefill. One in flight at a time:
                # it holds a slot from its first chunk, and serializing
                # keeps prefill compute from flooding the decode stream.
                if self._chunked is not None or not self._free:
                    back.append(req)
                elif not self._start_chunked(req, key):
                    back.append(req)
                continue
            try:
                bucket = _bucket_for(len(req.prompt))
            except Exception as e:  # bad request fails alone, takes no slot
                _fail(req, e)
                continue
            by_bucket.setdefault(bucket, []).append((req, key))
        groups = [chunk[i:i + self._group_pad]
                  for chunk in by_bucket.values()
                  for i in range(0, len(chunk), self._group_pad)]
        for group in groups:
            if self.role == "prefill":
                # prefill specialist: ONE batched prefill, then export each
                # row's KV blocks + first token over the wire — no slot, no
                # arena reservation, no decode. Ownership moves to the
                # handoff sink (the fleet routes it to a decode replica).
                try:
                    keys = jnp.stack([k for _, k in group])
                    small, first = self._prefill_group(
                        [r.prompt for r, _ in group],
                        [r.temperature for r, _ in group], keys,
                        first=group[0][0])
                except Exception as e:
                    for req, _ in group:
                        _fail(req, e)
                    continue
                self._export_group(group, small, first)
                continue
            # reserve the worst case BEFORE spending prefill compute;
            # exhaustion is back-pressure (the request stays pending and
            # retries as retirements free blocks), not an error
            reserved: List[KVReservation] = []
            admit: List[Tuple[_Request, Any]] = []
            for req, key in group:
                try:
                    res = self.kv.reserve(len(req.prompt) + req.max_new_tokens)
                except FleetSaturated:
                    back.append(req)
                    continue
                except Exception as e:
                    _fail(req, e)
                    continue
                admit.append((req, key))
                reserved.append(res)
            group = admit
            if not group:
                continue
            try:
                keys = jnp.stack([k for _, k in group])
                # its launch's wall time goes to ``serving_prefill_seconds``
                # (the tokens surface later via the pipelined 'first' event)
                small, first = self._prefill_group(
                    [r.prompt for r, _ in group],
                    [r.temperature for r, _ in group], keys,
                    first=group[0][0])
            except Exception as e:  # whole-group failure takes no slots
                for res in reserved:
                    self.kv.release(res)
                for req, _ in group:
                    _fail(req, e)
                continue
            n = len(group)
            slots = [self._free.pop() for _ in range(n)]
            lens = [len(r.prompt) for r, _ in group]
            slots_arr = jnp.asarray(slots, dtype=jnp.int32)
            true_lens_arr = jnp.asarray(lens, dtype=jnp.int32)
            # as wide as the bucket the prefill padded the prompts to
            block_ids = self.kv.bind(slots, reserved, lens,
                                     _bucket_for(max(lens)))
            try:
                # drop the scalar cursor — adopt() resets the row cursors itself
                small = self.family.kv_of(small)
                first_n = first[:n]
                self.cache, self.last_tok, self.temps, self.rngs = \
                    self._launch(
                        "adopt", self._adopt_fn,
                        self.cache, small, *map(jnp.asarray, block_ids),
                        slots_arr, true_lens_arr,
                        self.last_tok, self.temps, self.rngs, first_n,
                        jnp.asarray([r.temperature for r, _ in group],
                                    dtype=jnp.float32),
                        jnp.stack([jax.random.fold_in(k, 1)
                                   for _, k in group]))
                if self.spec_k:
                    # the draft must adopt the same prompts before any spec
                    # round includes these rows; a failure here is engine
                    # state corruption, so it propagates to the loop's
                    # catch-all (fail everything, close) rather than being
                    # swallowed per-group
                    dsmall, _ = self._prefill_group(
                        [r.prompt for r, _ in group],
                        [r.temperature for r, _ in group], keys, draft=True)
                    self.draft_cache = self._launch(
                        "draft_adopt", self._draft_adopt_fn,
                        self.draft_cache, self.family.kv_of(dsmall),
                        slots_arr, true_lens_arr)
            except Exception as e:
                # Adopt failed AFTER the slots were popped: these requests
                # are in neither _active nor the pending queue, so _shutdown
                # could never fail them — callers would block until their
                # result() timeout. Restore the slots and fail the group now.
                self._free.extend(slots)
                for slot in slots:
                    self.kv.release(slot)
                for req, _ in group:
                    _fail(req, e)
                continue
            try:
                first_n.copy_to_host_async()
            except Exception:
                pass
            # activate NOW (before the first-token value is on host): the
            # next chunk dispatch must include these rows in its snapshot
            now = time.perf_counter()
            for (req, _), slot in zip(group, slots):
                self._join(req, slot, now)
            events.append(("first", first_n,
                           [(req, slot) for (req, _), slot in zip(group, slots)],
                           now))
        if back:
            # requeue at the FRONT in arrival order: these requests lost no
            # place in line — they only wait for arena blocks or for the
            # (serialized) chunked-prefill lane to free up
            for r in reversed(back):
                self._pending.appendleft(r)
            self._set_queue_gauge()
        self._set_occupancy()
        return events, list(by_bucket)

    # -- KV handoff: prefill-role export (ISSUE 18) --------------------------
    def _export_group(self, group, small, first) -> None:
        """Fetch a prefill group's cache rows + first tokens to host and
        ship each request over the wire. The host fetch is a deliberate
        synchronous round trip: a prefill specialist has no decode lane to
        starve, and the wire serialization needs the bytes anyway."""
        first_host = np.asarray(first)
        host = {nm: {"k": np.asarray(l["attention"]["k"]),
                     "v": np.asarray(l["attention"]["v"])}
                for nm, l in small.items()}
        for i, (req, _) in enumerate(group):
            self._ship(req,
                       {nm: {"k": d["k"][i], "v": d["v"][i]}
                        for nm, d in host.items()},
                       int(first_host[i]))

    def _ship(self, req: _Request, row_cache: Dict[str, Any],
              first_token: int) -> None:
        """Export ONE prefilled request ([max_seq, h, d] contiguous rows
        per layer) to the KV wire format and hand it to the sink. The sink
        call is synchronous — when it returns without raising, ownership
        has transferred (a decode replica holds the import); any failure
        fails the request here, where its future still has an owner."""
        from .kv_wire import export_kv

        sink = self.handoff_sink
        if sink is None:
            _fail(req, RuntimeError(
                "prefill engine has no handoff_sink — a prefill-role "
                "replica cannot serve decode itself"))
            return
        try:
            t0 = time.perf_counter()
            blob = export_kv(
                row_cache, prompt_len=len(req.prompt),
                block_t=self.kv_block_t, kv_dtype=self.kv_dtype,
                first_token=first_token, model_id=self.model_id)
            req.kv_blob = blob
            sink(req, blob)
        except Exception as e:
            _fail(req, e)
            return
        dt = time.perf_counter() - t0
        METRICS.counter("serving_kv_handoff_total").inc()
        METRICS.histogram("serving_kv_handoff_bytes",
                          buckets=HANDOFF_BYTES_BUCKETS).observe(
            float(len(blob)))
        METRICS.histogram("serving_kv_handoff_seconds",
                          buckets=PREFILL_BUCKETS_S).observe(
            dt, trace_id=_trace_id(req))
        _ev(req, "kv_handoff", bytes=len(blob))

    def _build_draft_full_prefill(self):
        dmodel = self._draft_family.prefill_model

        @jax.jit
        def draft_full(params, cache, ids):
            _, updated = dmodel.apply(
                {"params": params, "cache": cache}, ids,
                mutable=["cache"])
            return updated["cache"]

        return draft_full

    # -- chunked prefill (ISSUE 12) ------------------------------------------
    def _build_chunk_prefill(self):
        model = self.family.prefill_model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chunk_prefill(params, cache, ids, first_idx, temperature, key):
            logits, updated = model.apply(
                {"params": params, "cache": cache}, ids, mutable=["cache"])
            # only the LAST chunk's call reads a real token (first_idx =
            # the prompt's true last position inside that chunk); earlier
            # chunks pass 0 and discard the result
            lg = logits[0, first_idx]
            greedy = jnp.argmax(lg).astype(jnp.int32)
            sampled = jax.random.categorical(
                key, lg / jnp.maximum(temperature, 1e-6)).astype(jnp.int32)
            return updated["cache"], jnp.where(
                temperature > 0.0, sampled, greedy)

        return chunk_prefill

    def _start_chunked(self, req: _Request, key) -> bool:
        """Claim a slot and the worst-case KV reservation for one long
        prompt and install it as THE in-flight chunked prefill — the actual
        chunk dispatches happen one per engine iteration from
        :meth:`_advance_chunked` so decode keeps ticking in between.
        Returns False when the arena cannot reserve yet (caller requeues);
        a structurally impossible request fails and returns True."""
        # a prefill specialist never decodes: it reserves nothing — the
        # decode replica that imports the wire blob reserves there
        tokens = (0 if self.role == "prefill"
                  else len(req.prompt) + req.max_new_tokens)
        try:
            res = self.kv.reserve(tokens)
        except FleetSaturated:
            return False
        except Exception as e:
            _fail(req, e)
            return True
        slot = self._free.pop()
        self.kv.hold(slot, res)
        in_arena = self.family.prefills_in_arena    # no private cache, then
        self._chunked = _ChunkedPrefill(
            req=req, slot=slot, key=key, res=res,
            cache=None if in_arena else self.family.prefill_cache(1))
        _ev(req, "chunked_prefill_start", slot=slot,
            chunks=-(-len(req.prompt) // self.prefill_chunk))
        return True

    def _abort_chunked(self, cp: _ChunkedPrefill) -> None:
        """Release a mid-prefill request's slot and KV; the caller
        completes/fails the request itself."""
        self.kv.release(cp.slot)
        self._free.append(cp.slot)
        self._chunked = None

    def _advance_chunked(self) -> List[Tuple[str, Any, Any, float]]:
        """Dispatch ONE prefill chunk for the in-flight long prompt; on the
        last chunk, adopt into the shared cache and activate the slot.
        Returns the pipelined 'first' event when the adoption happens."""
        cp = self._chunked
        req = cp.req
        if req.done.is_set():  # failed/completed elsewhere; just clean up
            self._abort_chunked(cp)
            return []
        if req.cancel_requested:
            req.finish_reason = "cancelled"
            METRICS.counter("serving_cancelled_total").inc()
            _ev(req, "cancelled", stage="prefill")
            self._abort_chunked(cp)
            _fail(req, RequestCancelled("cancelled during chunked prefill"))
            return []
        if req.expired():
            req.finish_reason = "deadline"
            METRICS.counter("serving_deadline_expired_total",
                            stage="prefill").inc()
            _ev(req, "deadline_expired", stage="prefill")
            self._abort_chunked(cp)
            _fail(req, DeadlineExceeded(
                "deadline expired during chunked prefill"))
            return []
        if self.family.prefills_in_arena:
            return self._advance_in_arena(cp)
        if self._chunk_prefill_fn is None:
            self._chunk_prefill_fn = self._build_chunk_prefill()
        n = len(req.prompt)
        c = self.prefill_chunk
        start = cp.pos
        seg = req.prompt[start:start + c]
        ids = np.zeros((1, c), np.int32)
        ids[0, :len(seg)] = seg
        last = start + c >= n
        # padding past the prompt (final chunk only) writes garbage KV at
        # positions >= n; adoption sets the cursor to n, so the mask hides
        # it until decode overwrites position n onward
        first_idx = (n - 1) - start if last else 0
        cp.cache, first = self._launch(
            "chunk_prefill", self._chunk_prefill_fn,
            self.params, cp.cache, jnp.asarray(ids),
            jnp.asarray(first_idx, jnp.int32),
            jnp.asarray(req.temperature, jnp.float32), cp.key)
        cp.pos = start + c
        METRICS.counter("serving_prefill_chunks_total").inc()
        _ev(req, "prefill_chunk", start=start)
        if not last:
            return []
        if self.role == "prefill":
            # last chunk of a long prompt on a prefill specialist: export
            # instead of adopting — the decode replica owns it from here
            host = {nm: {"k": np.asarray(l["attention"]["k"])[0],
                         "v": np.asarray(l["attention"]["v"])[0]}
                    for nm, l in cp.cache.items()}
            tok = int(np.asarray(first))
            self._abort_chunked(cp)
            self._ship(req, host, tok)
            return []
        # -- last chunk: adopt + activate -----------------------------------
        slot = cp.slot
        first_arr = first[None]
        small = self.family.kv_of(cp.cache)
        slots_arr = jnp.asarray([slot], jnp.int32)
        true_lens_arr = jnp.asarray([n], jnp.int32)
        # whole blocks of the padded prompt: block_t divides the chunk
        block_ids = self.kv.bind([slot], [cp.res], [n], cp.pos)
        self.cache, self.last_tok, self.temps, self.rngs = self._launch(
            "adopt", self._adopt_fn,
            self.cache, small, *map(jnp.asarray, block_ids), slots_arr,
            true_lens_arr, self.last_tok, self.temps, self.rngs, first_arr,
            jnp.asarray([req.temperature], jnp.float32),
            jax.random.fold_in(cp.key, 1)[None])
        if self.spec_k:
            # the draft adopts the full prompt in one forward (its whole
            # point is being small; chunking IT would serialize more
            # dispatches for no decode-lane benefit)
            dzero = self._draft_family.prefill_cache(1)
            if self._draft_full_prefill_fn is None:
                self._draft_full_prefill_fn = self._build_draft_full_prefill()
            dids = np.zeros((1, cp.pos), np.int32)
            dids[0, :n] = req.prompt
            dsmall = self._launch(
                "draft_prefill", self._draft_full_prefill_fn,
                self._draft_params, dzero, jnp.asarray(dids))
            self.draft_cache = self._launch(
                "draft_adopt", self._draft_adopt_fn,
                self.draft_cache, self.family.kv_of(dsmall), slots_arr,
                true_lens_arr)
        return self._activate_chunked(req, slot, first_arr)

    def _activate_chunked(self, req: _Request, slot: int, first: Any
                          ) -> List[Tuple[str, Any, Any, float]]:
        """A chunk-prefilled request joins the decode batch: the lane is
        free again, and its first token (``first``: what the host fetches
        for it) becomes the pipelined 'first' event."""
        try:
            for arr in jax.tree.leaves(first):
                arr.copy_to_host_async()
        except Exception:
            pass
        now = time.perf_counter()
        self._join(req, slot, now)
        self._chunked = None
        self._set_occupancy()
        return [("first", first, [(req, slot)], now)]

    def _advance_in_arena(self, cp: _ChunkedPrefill
                          ) -> List[Tuple[str, Any, Any, float]]:
        """One chunk of a prompt whose family prefills straight into the
        arenas (no private cache, no adopt): the owner grants the chunk its
        blocks of both kinds and says which tables it reads and writes
        (``SlotKV.chunk_tables``), the family's chunk program runs — ONE
        program a chunk shape and view width, whatever the prompt's length
        — and, after the last chunk, the row's table becomes the one decode
        dispatches see and the slot is activated."""
        req, slot = cp.req, cp.slot
        if self._chunk_prefill_fn is None:
            self._chunk_prefill_fn = self.family.build_chunk_prefill()
        n, c = len(req.prompt), self.prefill_chunk
        start = cp.pos
        end = min(start + c, n)
        ids = np.zeros((c,), np.int32)
        ids[:end - start] = req.prompt[start:end]
        tables = self.kv.chunk_tables(slot, start, end, c)
        self.cache, first, stats = self._launch(
            "chunk_prefill", self._chunk_prefill_fn,
            self.params, self.cache, jnp.asarray(ids),
            jnp.asarray(start, jnp.int32), jnp.asarray(end - start, jnp.int32),
            jnp.asarray(req.temperature, jnp.float32), cp.key,
            *map(jnp.asarray, tables))
        cp.stats = stats if cp.stats is None else cp.stats + stats
        cp.pos = start + c
        METRICS.counter("serving_prefill_chunks_total").inc()
        _ev(req, "prefill_chunk", start=start)
        if end < n:
            return []
        self.kv.bind([slot], [cp.res], [n])
        self.cache, self.last_tok, self.temps, self.rngs = self._launch(
            "activate", self._activate_fn,
            self.cache, self.last_tok, self.temps, self.rngs,
            jnp.asarray(slot, jnp.int32), jnp.asarray(n, jnp.int32), first,
            jnp.asarray(req.temperature, jnp.float32),
            jax.random.fold_in(cp.key, 1))
        return self._activate_chunked(req, slot, (first[None], cp.stats))

    # -- KV handoff: decode-role import (ISSUE 18) ---------------------------
    def _admit_imports(self) -> List[Tuple[str, Any, Any, float]]:
        """Admit queued KV-wire imports into free slots: reserve arena
        blocks (normal back-pressure — an exhausted arena leaves the import
        queued and retries as retirements free blocks), grant the prompt's
        blocks, scatter the wire payload in one jitted call, and activate.
        The 'first' event carries the PREFILL replica's first token so the
        decode side emits it through the standard event path (TTFT from
        the original submit instant — handoff latency is inside it)."""
        events: List[Tuple[str, Any, Any, float]] = []
        quant = self.kv_dtype == "int8"
        while self._imports and self._free:
            imp = self._imports[0]
            req = imp.req
            if req.done.is_set():
                self._imports.popleft()
                continue
            if req.cancel_requested:
                self._imports.popleft()
                req.finish_reason = "cancelled"
                METRICS.counter("serving_cancelled_total").inc()
                _ev(req, "cancelled", stage="import")
                _fail(req, RequestCancelled("cancelled before KV import"))
                continue
            if req.expired():
                self._imports.popleft()
                req.finish_reason = "deadline"
                METRICS.counter("serving_deadline_expired_total",
                                stage="queued").inc()
                _ev(req, "deadline_expired", stage="import")
                _fail(req, DeadlineExceeded(
                    "deadline expired before KV import"))
                continue
            n = len(req.prompt)
            try:
                res = self.kv.reserve(n + req.max_new_tokens)
            except FleetSaturated:
                break  # no blocks yet; the import keeps its place in line
            except Exception as e:
                self._imports.popleft()
                _fail(req, e)
                continue
            self._imports.popleft()
            slot = self._free.pop()
            block_ids = self.kv.bind([slot], [res], [n])[0][0]
            nb = len(block_ids)
            try:
                if any(a.shape[0] != nb for a in imp.arrays.values()):
                    raise ValueError(
                        f"wire carries a block count != {nb} for "
                        f"prompt_len {n}")
                wire = {}
                for i in range(self.cfg.n_layers):
                    nm = f"block_{i}"
                    entry = {"k": jnp.asarray(imp.arrays[f"{nm}/k"]),
                             "v": jnp.asarray(imp.arrays[f"{nm}/v"])}
                    if quant:
                        entry["k_scale"] = jnp.asarray(
                            imp.arrays[f"{nm}/k_scale"])
                        entry["v_scale"] = jnp.asarray(
                            imp.arrays[f"{nm}/v_scale"])
                    wire[nm] = entry
                self._rng_counter += 1
                key = jax.random.fold_in(self._base_rng, self._rng_counter)
                (self.cache, self.last_tok, self.temps, self.rngs) = \
                    self._launch(
                        "import", self._import_fn,
                        self.cache, wire, jnp.asarray(block_ids),
                        self.last_tok, self.temps, self.rngs,
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray(n, jnp.int32),
                        jnp.asarray(int(imp.manifest["first_token"]),
                                    jnp.int32),
                        jnp.asarray(req.temperature, jnp.float32),
                        jax.random.fold_in(key, 1))
                if self.spec_k:
                    # the wire carries no draft KV: the draft re-prefills
                    # the prompt locally in one forward (it is small by
                    # construction — that is the draft's whole point)
                    dzero = self._draft_family.prefill_cache(1)
                    if self._draft_full_prefill_fn is None:
                        self._draft_full_prefill_fn = \
                            self._build_draft_full_prefill()
                    pad = nb * self.kv_block_t
                    dids = np.zeros((1, pad), np.int32)
                    dids[0, :n] = req.prompt
                    dsmall = self._launch(
                        "draft_prefill", self._draft_full_prefill_fn,
                        self._draft_params, dzero, jnp.asarray(dids))
                    self.draft_cache = self._launch(
                        "draft_adopt", self._draft_adopt_fn,
                        self.draft_cache, self.family.kv_of(dsmall),
                        jnp.asarray([slot], jnp.int32),
                        jnp.asarray([n], jnp.int32))
            except Exception as e:
                self._free.append(slot)
                self.kv.release(slot)
                _fail(req, e)
                continue
            now = time.perf_counter()
            METRICS.counter("serving_kv_import_total").inc()
            self._join(req, slot, now, "kv_import", blocks=int(nb))
            events.append(("first",
                           np.asarray([imp.manifest["first_token"]], np.int32),
                           [(req, slot)], now))
        self._set_occupancy()
        return events

    def _run_decode(self, tables: Tuple[Any, ...]) -> Tuple[str, Any]:
        """One decode chunk, or one speculative round, of every slot on the
        engine's state, reading the arena through ``tables`` (the block
        table's first columns; empty for the contiguous cache). Returns the
        event's kind and what the host fetches for it."""
        if self.spec_k:
            (self.cache, self.draft_cache, self.last_tok, self.rngs, toks,
             acc) = self._launch(
                "spec", self._spec_fn,
                self.params, self._draft_params, self.cache, self.draft_cache,
                self.last_tok, self.temps, self.rngs, *tables)
            return "spec", (toks, acc)
        self.cache, self.last_tok, self.rngs, toks, *stats = self._launch(
            "step", self._step_fn,
            self.params, self.cache, self.last_tok, self.temps, self.rngs,
            *tables)
        return "chunk", (toks, *stats) if stats else toks

    def _join(self, req: _Request, slot: int, now: float,
              how: str = "prefill_done", **attrs: Any) -> None:
        """A request whose KV is in place joins the decode batch in
        ``slot``: the next dispatch has the row in its snapshot."""
        self._active[slot] = req
        if req.submit_at is not None:
            METRICS.histogram(
                "serving_queue_wait_seconds", buckets=QUEUE_WAIT_BUCKETS,
            ).observe(now - req.submit_at, trace_id=_trace_id(req))
        _ev(req, "admitted", slot=slot)
        _ev(req, how, **attrs)

    def _set_occupancy(self) -> None:
        active = len(self._active)
        METRICS.gauge("serving_continuous_active_slots",
                      replica=self.engine_id).set(active)
        METRICS.gauge("serving_slot_occupancy", replica=self.engine_id).set(
            active / self.slots if self.slots else 0.0)

    def _retire(self, slot: int) -> None:
        req = self._active.pop(slot)
        self._free.append(slot)
        self.kv.release(slot)
        req.done_at = time.perf_counter()
        if req.finish_reason is None:
            req.finish_reason = "ok"
        if req.submit_at is not None:
            METRICS.histogram("serving_request_seconds").observe(
                req.done_at - req.submit_at, trace_id=_trace_id(req))
        if req.span is not None:
            _ev(req, "retired", slot=slot)
            req.span.set("generated_tokens", len(req.tokens))
            req.span.set("finish_reason", req.finish_reason)
            TRACER.end_span(req.span)
            req.span = None
        req.done.set()
        req._notify()
        METRICS.counter("serving_continuous_requests_total").inc()
        self._set_occupancy()

    def _set_queue_gauge(self) -> None:
        # every _pending mutation must republish the depth: the router's
        # least-loaded policy reads this gauge, and a stale value after a
        # reap leaves a healthy replica advertising phantom load (so no
        # breaker probe ever routes back to it)
        METRICS.gauge("serving_queue_depth",
                      replica=self.engine_id).set(len(self._pending))

    def _reap_pending(self) -> None:
        """Shed queued requests that will never need a slot: expired
        deadlines fail fast with DeadlineExceeded, abandoned clients with
        RequestCancelled — neither ever occupies a decode row."""
        if not self._pending:
            return
        kept: "collections.deque[_Request]" = collections.deque()
        for req in self._pending:
            if req.cancel_requested:
                METRICS.counter("serving_cancelled_total").inc()
                _ev(req, "cancelled", stage="queued")
                req.finish_reason = "cancelled"
                _fail(req, RequestCancelled("cancelled while queued"))
            elif req.expired():
                METRICS.counter("serving_deadline_expired_total",
                                stage="queued").inc()
                _ev(req, "deadline_expired", stage="queued")
                req.finish_reason = "deadline"
                _fail(req, DeadlineExceeded(
                    "deadline expired while queued (never admitted)"))
            else:
                kept.append(req)
        self._pending = kept
        self._set_queue_gauge()

    def _reap_active(self) -> None:
        """Free the slot of any in-flight request whose deadline expired
        or whose future was abandoned — within ONE loop iteration (≤ one
        decode chunk) of the event. The request completes with its partial
        tokens (done, no error); tokens the pipeline already dispatched
        for the row are counted as wasted when their events surface."""
        for slot, req in list(self._active.items()):
            if req.cancel_requested:
                req.finish_reason = "cancelled"
                METRICS.counter("serving_cancelled_total").inc()
                _ev(req, "cancelled", stage="decoding",
                    partial_tokens=len(req.tokens))
                self._retire(slot)
            elif req.expired():
                req.finish_reason = "deadline"
                METRICS.counter("serving_deadline_expired_total",
                                stage="decoding").inc()
                _ev(req, "deadline_expired", stage="decoding",
                    partial_tokens=len(req.tokens))
                self._retire(slot)

    @property
    def _batch_cap(self) -> int:
        """Queue depth at which BATCH requests shed; interactive keeps the
        full ``max_pending`` — the reserved fraction."""
        return max(1, int(self.max_pending * (1.0 - self.interactive_reserve)))

    def _enqueue_pendings(self, reqs: List[_Request]) -> None:
        for req in reqs:
            if self.max_pending:
                depth = len(self._pending)
                cap = (self._batch_cap if req.priority == "batch"
                       else self.max_pending)
                if depth >= cap:
                    METRICS.counter("serving_shed_total",
                                    priority=req.priority).inc()
                    _ev(req, "shed", priority=req.priority, depth=depth)
                    _fail(req, FleetSaturated(
                        f"engine queue full ({depth} >= {cap} "
                        f"for priority={req.priority})"))
                    continue
            _ev(req, "dequeued")
            self._pending.append(req)

    def _next_wave(self, n: int) -> List[_Request]:
        """Interactive-first admission: fill up to ``n`` free slots from
        the interactive pendings before any batch request is considered,
        so a batch backlog cannot starve interactive TTFT."""
        if len(self._pending) <= n:
            wave = list(self._pending)
            self._pending.clear()
            return wave
        wave = [r for r in self._pending if r.priority != "batch"][:n]
        if len(wave) < n:
            wave.extend([r for r in self._pending
                         if r.priority == "batch"][: n - len(wave)])
        for r in wave:
            self._pending.remove(r)
        return wave

    def _shutdown(self, cause: str) -> None:
        """Fail everything in flight, pending, and still queued — all with
        the SAME cause, so a device failure is debuggable from any failed
        caller, not only the in-flight ones."""
        if self._chunked is not None:
            # mid-prefill request: in neither _active nor _pending — it
            # would hang its caller if this path forgot it
            cp = self._chunked
            self._abort_chunked(cp)
            _fail(cp.req, EngineClosed(cause))
        for req in self._active.values():
            _fail(req, EngineClosed(cause))
        self._active.clear()
        while self._pending:
            _fail(self._pending.popleft(), EngineClosed(cause))
        while self._imports:
            _fail(self._imports.popleft().req, EngineClosed(cause))
        self._set_queue_gauge()
        while True:
            try:
                rest = self._queue.get_nowait()
            except queue.Empty:
                return
            if isinstance(rest, _Import):
                _fail(rest.req, EngineClosed(cause))
            elif rest is not None and rest is not _DRAIN:
                for req in rest:
                    _fail(req, EngineClosed(cause))

    def _process_event(self, event: Tuple[str, Any, Any, float]) -> None:
        """Consume one pipelined event in dispatch order. ``first``: fetch
        an admission group's first tokens (appended before any of that
        request's chunk tokens — FIFO order guarantees it). ``chunk``:
        fetch a token block and retire against the DISPATCH-TIME snapshot —
        a row whose request finished in an earlier event is a discarded
        tail; a row adopted after the dispatch is not in the snapshot."""
        kind, dev, meta, dispatched_at = event
        widths = marks = cursors = stats = None
        if kind != "first":
            self._decodes_in_flight -= 1
        if self.family.has_stats:
            # the family's counters ride with the tokens
            dev, stats = dev
        with profiling.annotate("serving.engine.fetch", kind=kind):
            # host fetch (async copy started at dispatch)
            if isinstance(dev, tuple):
                # the event says how many of each row's tokens are real: a
                # speculative round's [slots, spec_k] candidates with the
                # accepted width m (1..spec_k; the rest were refuted by the
                # verify forward), or the blocks each slot completed in the
                # dispatch, with a mark beside each token and the device's
                # cursors after it
                block, widths, *rest = (np.asarray(a) for a in dev)
                if rest:
                    marks, cursors = rest
            else:
                block = np.asarray(dev)
            if stats is not None:
                stats = np.asarray(stats)
        now = time.perf_counter()
        with profiling.annotate("serving.engine.deliver", kind=kind,
                                rows=int(block.size)) as span:
            if kind == "first":
                # a family whose prefill yields no token fetched only the
                # prompt's counters: the first token comes with a block
                tokens, retired = (self._deliver_first(meta, block, now)
                                   if self.family.prefill_yields_token else (0, 0))
            else:
                # dispatch→fetch-complete latency of one pipelined decode
                # chunk (``now`` is where the fetch region closed)
                METRICS.histogram(
                    "serving_decode_chunk_seconds",
                    buckets=DECODE_CHUNK_BUCKETS
                ).observe(now - dispatched_at)
                tokens, retired = self._deliver_block(
                    meta, block, widths, now, marks, drafted=kind == "spec")
                if cursors is not None:
                    # the cursors moved by what the slots committed: the
                    # bound the next grants start from comes down to them
                    for slot, req in meta.items():
                        if self._active.get(slot) is req:
                            self.kv.settle(slot, int(cursors[slot])
                                           + self._decodes_in_flight * self._moves)
            span.set_metadata(tokens=tokens, retired=retired)
            if stats is not None:
                # over the event's live tokens: a decode chunk's rows, or
                # the prompt a first token closes
                span.set_metadata(**self.family.account(
                    [int(v) for v in stats],
                    sum(len(r.prompt) for r, _ in meta) if kind == "first"
                    else len(meta) * block.shape[1]))

    def _deliver_first(self, pairs, block, now: float) -> Tuple[int, int]:
        """An admission group's first tokens to their requests. Returns
        (tokens appended to live requests, requests retired)."""
        tokens = retired = 0
        for (req, slot), tok in zip(pairs, block):
            if req.done.is_set():
                # reaped (deadline/cancel) between admission and this
                # event — its prefill token was computed for nobody
                if req.finish_reason in ("deadline", "cancelled"):
                    METRICS.counter(
                        "serving_wasted_decode_tokens_total").inc()
                continue
            req.tokens.append(int(tok))
            tokens += 1
            METRICS.counter("serving_tokens_out_total").inc()
            self._note_first(req, now)
            hit_eos = req.eos_id is not None and req.tokens[-1] == req.eos_id
            if req.max_new_tokens <= 1 or hit_eos:
                # the slot was activated at admission, so the normal
                # retirement path applies
                self._retire(slot)
                retired += 1
        return tokens, retired

    def _deliver_block(self, snapshot, block, widths, now: float,
                       marks=None, drafted: bool = False) -> Tuple[int, int]:
        """One decode dispatch's token block to the requests of its
        dispatch-time snapshot. ``widths`` [slots], where the event carries
        them, says how many of each row's tokens are real (None: the whole
        row); ``marks`` [slots, width] an integer beside each token;
        ``drafted``: the widths are a speculative round's accepted prefixes.
        Returns (tokens appended to live requests, requests retired)."""
        tokens = retired = 0
        for slot, req in snapshot.items():
            # usable tokens this row produced: the event's width, or the
            # whole chunk
            width = int(widths[slot]) if widths is not None else block.shape[1]
            if drafted and not req.done.is_set():
                # accept-rate numerators: spec_k - 1 verifiable drafts per
                # round; width - 1 of them accepted (the +1 is the target's
                # own token, drafted or not)
                METRICS.counter("serving_spec_tokens_drafted_total").inc(
                    self.spec_k - 1)
                if width > 1:
                    METRICS.counter("serving_spec_tokens_accepted_total").inc(
                        width - 1)
            if req.done.is_set():
                # retired in an earlier event; this row's whole block was
                # computed for nobody — the engine's "preempted work" cost
                METRICS.counter("serving_discarded_tail_tokens_total").inc(
                    width)
                if req.finish_reason in ("deadline", "cancelled"):
                    # tokens generated past an expired deadline / abandoned
                    # future — the goodput-loss counter, rolled up with the
                    # shed/expiry waste into the serving token-goodput view
                    # (monitoring/goodput.serving_goodput_view, surfaced at
                    # GET /debug/goodput and in the dashboard)
                    METRICS.counter("serving_wasted_decode_tokens_total").inc(
                        width)
                continue
            appended = 0
            for j in range(width):
                tok = int(block[slot, j])
                req.tokens.append(tok)
                if marks is not None:
                    req.reveal_passes.append(int(marks[slot, j]))
                appended += 1
                tokens += 1
                hit_eos = req.eos_id is not None and tok == req.eos_id
                if len(req.tokens) >= req.max_new_tokens or hit_eos:
                    # inter-token latency amortized over the block BEFORE
                    # _retire closes the span (one observe, count=n — the
                    # per-token path must not pay per-token metric calls)
                    self._note_tokens(req, appended, now)
                    self._retire(slot)
                    retired += 1
                    METRICS.counter(
                        "serving_discarded_tail_tokens_total"
                    ).inc(width - j - 1)
                    appended = 0
                    break
            if appended:
                self._note_tokens(req, appended, now)
        return tokens, retired

    def _note_first(self, req: _Request, now: float) -> None:
        """The request's first token reached the host."""
        req.first_token_at = req.last_token_at = now
        if req.submit_at is not None:
            METRICS.histogram(
                "serving_ttft_seconds", buckets=TTFT_BUCKETS
            ).observe(now - req.submit_at, trace_id=_trace_id(req))
        _ev(req, "first_token")

    def _note_tokens(self, req: _Request, n: int, now: float) -> None:
        """``n`` tokens of one event reached ``req``: the gap between tokens
        is the time since the last event's over the ``n`` it brought. The
        first tokens of a request whose prefill yielded none are its first
        block's: they stamp TTFT, and no gap among themselves."""
        METRICS.counter("serving_tokens_out_total").inc(n)
        if req.first_token_at is None:
            self._note_first(req, now)
        elif req.last_token_at is not None:
            METRICS.histogram(
                "serving_inter_token_seconds", buckets=ITL_BUCKETS
            ).observe((now - req.last_token_at) / n, count=n,
                      trace_id=_trace_id(req))
        req.last_token_at = now

    def _loop(self) -> None:
        """The engine thread: turns until shutdown or a completed drain.
        Every phase of a turn is a ``serving.engine.*`` region of the
        profiler's trace (docs/OBSERVABILITY.md), so a capture says what
        the host was doing whenever the device sat idle."""
        events: "collections.deque[Tuple[str, Any, Any, float]]" = collections.deque()
        while True:
            with profiling.annotate("serving.engine.turn"):
                if not self._turn(events):
                    return

    def _take(self, item: Any) -> int:
        """One item off the thread-safe queue into the engine's own state.
        Returns how many requests it carried."""
        if item is _DRAIN:
            # submits racing the drain land BEFORE the sentinel (submit
            # checks _closed under the lock that also enqueues it), so
            # everything still queued here is part of the handoff set
            self._draining = True
            return 0
        if isinstance(item, _Import):
            self._imports.append(item)
            return 1
        self._enqueue_pendings(item)
        return len(item)

    def _turn(self, events: "collections.deque[Tuple[str, Any, Any, float]]"
              ) -> bool:
        """One pass of the engine loop; False once the loop is over."""

        # drain arrivals into the pending deque; block only when fully
        # idle (no busy-wait). Coalescing the drain is what lets a burst
        # of single submits admit as ONE batched prefill.
        item = _NOTHING
        if not (self._active or self._pending or events or self._draining
                or self._chunked or self._imports):
            with profiling.annotate("serving.engine.idle"):
                item = self._queue.get()
        with profiling.annotate("serving.engine.drain") as span:
            arrivals = 0
            while True:
                if item is _NOTHING:
                    try:
                        item = self._queue.get_nowait()
                    except queue.Empty:
                        break
                if item is None:
                    self._shutdown("batcher closed mid-flight")
                    return False
                arrivals += self._take(item)
                item = _NOTHING
            span.set_metadata(arrivals=arrivals)
        self._set_queue_gauge()
        try:
            if self.fail_next_step:
                # chaos crash_replica_mid_decode: poison the iteration;
                # the handler below fails everything and closes the
                # engine, exactly like a real device/RPC death
                self.fail_next_step = False
                raise RuntimeError("chaos: replica crashed mid-decode")
            if self.step_delay_s > 0:
                # chaos slow_replica: stall the dispatch loop so
                # deadlines expire and the fleet's breaker sees a
                # slow replica
                time.sleep(min(self.step_delay_s, 5.0))
            # reap BEFORE admission: an expired queued request must
            # never take a slot, and an expired/abandoned in-flight
            # one frees its slot for this very wave
            with profiling.annotate("serving.engine.reap"):
                self._reap_pending()
                self._reap_active()
            if self._view_warmup == "asked" and not self._active:
                # compile the decode program at every view width: the cursors
                # and sampling state a run on all-trash tables moves are set
                # anew by the adopt that admits a request
                for tables in self.kv.warm_tables():
                    self._run_decode(tables)
                self._view_warmup = "done"
            dispatched = False
            if self._imports and self._free and not self._draining:
                # wire imports admit before fresh prompts: their
                # prefill compute is already spent — leaving them
                # queued behind new admissions would waste it twice
                with profiling.annotate("serving.engine.import"):
                    events.extend(self._admit_imports())
                dispatched = True
            batched = not self.family.prefills_in_arena
            if (self._free and self._pending and not self._draining
                    and (batched or self._chunked is None)):
                # a family that prefills every prompt in the one chunked
                # lane admits one request, and only when the lane is free:
                # a wave of more would be keyed, turned back and queued
                # again every turn
                wave = self._next_wave(len(self._free) if batched else 1)
                self._set_queue_gauge()
                events.extend(self._admit_wave(wave))
                dispatched = True
            if self._chunked is not None:
                # ONE prefill chunk per iteration, interleaved between
                # decode dispatches — TTFT of the chatty slots stops
                # being hostage to the longest prompt (drain included:
                # the mid-prefill request is in-flight work)
                with profiling.annotate("serving.engine.prefill_chunk"):
                    events.extend(self._advance_chunked())
                dispatched = True
            if self._active:
                # one CHUNK of decode steps (or one speculative round)
                # for every slot (inactive rows compute too — static
                # shapes are the TPU contract; their outputs are
                # discarded when processed against the snapshot)
                width = self.spec_k if self.spec_k else self.chunk
                with profiling.annotate("serving.engine.dispatch",
                                        rows=self.slots * width,
                                        live=len(self._active)) as span:
                    self.kv.advance(self._active, self._moves)
                    tables, stats = self.kv.dispatch_tables(self._active)
                    span.set_metadata(**stats)
                    kind, out = self._run_decode(tables)
                    try:
                        for arr in jax.tree.leaves(out):
                            arr.copy_to_host_async()
                    except Exception:
                        pass
                    events.append((kind, out, dict(self._active),
                                   time.perf_counter()))
                    self._decodes_in_flight += 1
                dispatched = True
            # keep the dispatch frontier at most ``pipeline`` chunks
            # ahead of the processed state; when nothing new could be
            # dispatched, drain one event so the pipeline empties
            while self._decodes_in_flight > self.pipeline:
                self._process_event(events.popleft())
            if not dispatched and events:
                self._process_event(events.popleft())
            if (self._draining and not self._active and not events
                    and self._chunked is None):
                # drain complete: every in-flight slot ran to its
                # budget/EOS; park the unserved pendings (futures still
                # open) for the caller and zero this replica's gauges.
                # Unadmitted KV imports park too — their ``kv_blob`` is
                # set, so the fleet re-imports them on a surviving
                # decode replica instead of re-running prefill.
                self._handoff.extend(self._pending)
                self._pending.clear()
                self._handoff.extend(imp.req for imp in self._imports
                                     if not imp.req.done.is_set())
                self._imports.clear()
                self._set_queue_gauge()
                self._set_occupancy()
                return False
        except Exception as e:
            # a device/RPC failure must not wedge the engine silently:
            # fail everything in flight, pending, and queued; refuse
            # new work
            with self._lock:
                self._closed = True
            self._shutdown(f"engine step failed: {e}")
            return False
        return True
