"""Model families of the serving engine.

``ContinuousBatcher`` (serving/continuous.py) owns the loop, the scheduler,
the admission path and the slots; ``paged.SlotKV`` owns the block
accounting. What neither knows is a model: which modules to build, what a fresh cache looks like,
which leaves of it an adopt, a rollback or an import touch, and which
device programs run a decode step or a prefill. A family is that knowledge
for one kind of configuration, found from the configuration's type
(:func:`family_for`): no flag and no environment variable names it.

``GptFamily`` (``GptConfig``): dense decoder, one kind of cache — a
contiguous ``[slots, max_seq]`` cache or one paged arena a layer — batched
bucket prefill on a private cache followed by an adopt, chunked prefill of
long prompts on a private cache, speculation, int8 arenas, KV hand-off.

``MimoFamily`` (``MimoConfig``): window and full attention mixed, so TWO
kinds of paged cache side by side (``paged.WindowRings`` beside the block
table), every prompt prefilled in fixed-size chunks that write straight
into the arenas, and an expert layer's counters riding home with the
tokens. It has no private prefill cache, so nothing of it is adopted.

``EvaFamily`` (``EvaConfig``): EVA attention, so again two kinds of paged
cache but other ones: the LOCAL kind holds a slot's current aligned window
only (``paged.AlignedWindows``), the SUMMARY kind a row a chunk of every
window the slot has left (the block table at a stride). Prompts prefill in
chunks straight into the arenas, as the MiMo family's.

``SdarFamily`` (``SdarConfig``): generation by diffusion over blocks. One
kind of cache (the block table alone), prompts prefilled in chunks straight
into the arena, an expert layer with EVERY expert held, and a decode
program that carries per-slot block state beside the arenas (the block's
ids, which are still masked, the pass that revealed each): a dispatch is a
fixed number of forward passes, and what it hands a slot is the blocks the
slot committed in it, a variable number of tokens.

``SarvamFamily`` (``SarvamConfig``): latent attention (MLA), so a third
kind of paged arena: the block table alone, ONE row a position and layer
(the normalised latent and the shared rotary key, 640 wide), no value
arena. Prompts prefill in chunks straight into the arena in the EXPANDED
form (the latent expanded to every head's key and value a block of keys at
a time, ``ops/latent_attention``); the decode step attends in the ABSORBED
form (each head's query folded into latent space, each page fetched once
for the scores and the context). The expert layer's counters and the
latent pages the decode attention fetched ride home with the tokens.

``NemotronFamily`` (``NemotronConfig``): Mamba-2 state-space layers beside
attention and latent expert layers, so a fourth kind of cache: a FIXED-size
state a slot and Mamba layer (``paged.SlotStates``), never paged, zeroed at
a prompt's first chunk and carried from chunk to chunk, read and written by
every decode step of a live row and left alone for the others. It is the
first family whose chunk program is told the slot. The attention layers'
arenas are the block table's, and prompts prefill in chunks straight into
both; the expert and state counters ride home with the tokens.

What the engine asks of a family, beside its programs: ``rings(lookahead,
engine_id)`` (the kind of cache that keeps a ring a slot beside the block
table, or None), ``states()`` (the kind that keeps a fixed-size state a
slot, or None), ``kv_stride`` (positions a row of the block table stands
for), ``prefills_in_arena`` (every prompt in chunks straight into the
arenas: no private cache, no adopt, no speculation or hand-off roles),
``has_stats`` (the step and the prefill return counters beside the tokens,
which ``account`` reads), ``prefill_yields_token`` (a prompt's last position
gives the first token; where not, the first token comes with the first
block the decode program hands out), ``kv_ahead`` (positions past a slot's
cursor that a step writes and reads: the block in flight), ``cursor_moves(
chunk)`` (the most positions a dispatch of ``chunk`` steps moves a cursor).
A step's fetched result is the tokens ``[slots, chunk]`` or a tuple
``(tokens [slots, width], widths [slots], marks [slots, width], cursors
[slots])``: how many of a row's tokens are real, a small integer beside each
token (the pass of its block that revealed it) and where the device's
cursors stand after the dispatch.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..models.evabyte import EvaConfig
from ..models.gpt import GptConfig, GptLM
from ..models.mimo import FULL, WINDOW, MimoConfig
from ..models.nemotron import NemotronConfig
from ..models.sarvam import SarvamConfig
from ..models.sdar import SdarConfig
from ..models import evabyte, mimo, nemotron, sarvam, sdar
from ..runtime.metrics import METRICS
from .paged import AlignedWindows, SlotStates, WindowRings


def sample_next(lg: jax.Array, temps: jax.Array, rngs: jax.Array):
    """One token a slot from ``lg`` [slots, vocab]: greedy where the
    temperature is 0, else categorical on the slot's own key. Returns
    (tokens int32 [slots], the advanced keys)."""
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    pairs = jax.vmap(jax.random.split)(rngs)   # [slots, 2, 2]
    rngs, keys = pairs[:, 0], pairs[:, 1]
    sampled = jax.vmap(
        lambda k, l, t: jax.random.categorical(k, l / jnp.maximum(t, 1e-6))
    )(keys, lg, temps).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy), rngs


def _contiguous_cache(cfg: GptConfig, rows: int, cursor: str) -> Dict[str, Any]:
    kv = (rows, cfg.max_seq, cfg.n_heads, cfg.head_dim)
    cur = (rows,) if cursor == "cursors" else ()
    return {
        f"block_{i}": {"attention": {
            "k": jnp.zeros(kv, cfg.dtype),
            "v": jnp.zeros(kv, cfg.dtype),
            cursor: jnp.zeros(cur, jnp.int32),    # a buffer of its own: donated
        }}
        for i in range(cfg.n_layers)
    }


class _TokenAStep:
    """The answers of a family whose decode step yields one token a slot."""

    #: a prompt's last position gives the request's first token
    prefill_yields_token = True
    #: a step writes and reads no position past its cursor
    kv_ahead = 0

    def cursor_moves(self, chunk: int) -> int:
        """A dispatch of ``chunk`` steps moves a cursor by as many positions."""
        return chunk

    def states(self) -> None:
        """No state a slot beside the blocks."""
        return None


class GptFamily(_TokenAStep):
    """``GptLM`` in the engine: the per-slot decode model, the scalar-cursor
    prefill model, their caches and the programs that move cache leaves."""

    #: prompts prefill in batches by bucket on a private cache, then adopt
    prefills_in_arena = False
    #: the step and the prefill return counters beside the tokens
    has_stats = False
    #: a row of the block table is a position
    kv_stride = 1

    def __init__(self, cfg: GptConfig, *, slots: int, paged: bool = False,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        self.cfg, self.slots, self.paged = cfg, slots, paged
        if paged and not kv_blocks:
            kv_blocks = slots * (cfg.max_seq // kv_block_t)      # a whole row a slot
        self.kv_blocks, self.kv_block_t, self.kv_dtype = kv_blocks, kv_block_t, kv_dtype
        if paged:
            self.model = GptLM(cfg, decode=True, per_slot=True, paged=True,
                               kv_blocks=kv_blocks + 1,
                               kv_block_t=kv_block_t,
                               kv_dtype=kv_dtype)
        else:
            self.model = GptLM(cfg, decode=True, per_slot=True)
        self.prefill_model = GptLM(cfg, decode=True)  # [1, P], scalar cursor

    def rings(self, lookahead: int, engine_id: str = "0") -> None:
        """One kind of cache: no ring beside the block table."""
        return None

    # -- caches ----------------------------------------------------------------
    def fresh_cache(self) -> Dict[str, Any]:
        cfg, S = self.cfg, self.slots
        if not self.paged:
            return _contiguous_cache(cfg, S, "cursors")
        arena = (self.kv_blocks + 1, self.kv_block_t, cfg.n_heads, cfg.head_dim)
        quant = self.kv_dtype == "int8"
        arena_dtype = jnp.int8 if quant else cfg.dtype

        def layer() -> Dict[str, Any]:
            att = {
                "k_arena": jnp.zeros(arena, arena_dtype),
                "v_arena": jnp.zeros(arena, arena_dtype),
                "cursors": jnp.zeros((S,), jnp.int32),
            }
            if quant:
                scale = arena[:3] + (1,)
                att["k_scale"] = jnp.zeros(scale, jnp.float32)
                att["v_scale"] = jnp.zeros(scale, jnp.float32)
            return {"attention": att}

        return {f"block_{i}": layer() for i in range(cfg.n_layers)}

    def prefill_cache(self, rows: int) -> Dict[str, Any]:
        """A zero ``[rows, max_seq]`` cache with one scalar cursor, as the
        prefill model takes it."""
        return _contiguous_cache(self.cfg, rows, "cursor")

    @staticmethod
    def kv_of(small: Dict[str, Any]) -> Dict[str, Any]:
        """A prefill cache without its scalar cursor (an adopt sets the
        rows' cursors itself)."""
        return {nm: {"attention": {"k": l["attention"]["k"],
                                   "v": l["attention"]["v"]}}
                for nm, l in small.items()}

    @staticmethod
    def rollback(cache, delta):
        out = {}
        for name, layer in cache.items():
            att = dict(layer["attention"])
            att["cursors"] = att["cursors"] - delta
            out[name] = {"attention": att}
        return out

    # -- device programs -----------------------------------------------------------
    def build_step(self, chunk: int):
        model = self.model
        paged = self.paged

        # donate cache+tok+rngs: without donation every dispatch COPIES the
        # full multi-GB KV cache into fresh output buffers (measured: the
        # copy, not the math, dominated chunked stepping)
        @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
        def step(params, cache, tok, temps, rngs, *tables):
            def one(carry, _):
                cache, tok, rngs = carry
                kwargs = {"block_tables": tables[0]} if paged else {}
                logits, updated = model.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    mutable=["cache"], **kwargs
                )
                with jax.named_scope("sample"):
                    nxt, rngs = sample_next(logits[:, -1], temps, rngs)
                return (updated["cache"], nxt, rngs), nxt

            (cache, tok, rngs), toks = jax.lax.scan(
                one, (cache, tok, rngs), None, length=chunk)
            return cache, tok, rngs, jnp.moveaxis(toks, 0, 1)  # [slots, chunk]

        return step

    def build_adopt(self):
        if self.paged:
            bt = self.kv_block_t
            quant = self.kv_dtype == "int8"

            @functools.partial(jax.jit, donate_argnums=(0, 5, 6, 7))
            def paged_adopt(cache, small, block_ids, slots, true_lens,
                            last_tok, temps, rngs, first_toks, temperatures,
                            slot_rngs):
                """Paged adoption: scatter each prefill row's first ``L``
                positions (``L = block_ids.shape[1] * block_t`` — the
                prompt bucket or the chunked-prefill span, both whole
                blocks by construction) into the arena rows named by
                ``block_ids``. Rows' trailing entries are the trash block,
                so bucket padding past the granted blocks lands in trash;
                padding inside the last granted block sits above the
                cursor, which the mask hides until decode overwrites it.
                int8 arenas quantize here with the SAME quantize_kv the KV
                wire exporter uses — a moved and a never-moved request land
                byte-identical int8 blocks."""
                from ..ops.kv_cache import quantize_kv

                n = slots.shape[0]
                nb = block_ids.shape[1]
                ids = block_ids.reshape(-1)
                out = {}
                for name, layer in cache.items():
                    att, small_att = layer["attention"], small[name]["attention"]
                    shape = small_att["k"].shape                 # [n_pad, max_seq, h, d]
                    seg_k = small_att["k"][:n, :nb * bt].reshape(
                        n * nb, bt, shape[2], shape[3])
                    seg_v = small_att["v"][:n, :nb * bt].reshape(
                        n * nb, bt, shape[2], shape[3])
                    upd = {"cursors": att["cursors"].at[slots].set(true_lens)}
                    if quant:
                        kq, ks = quantize_kv(seg_k)
                        vq, vs = quantize_kv(seg_v)
                        upd["k_arena"] = att["k_arena"].at[ids].set(kq)
                        upd["v_arena"] = att["v_arena"].at[ids].set(vq)
                        upd["k_scale"] = att["k_scale"].at[ids].set(ks)
                        upd["v_scale"] = att["v_scale"].at[ids].set(vs)
                    else:
                        upd["k_arena"] = att["k_arena"].at[ids].set(
                            seg_k.astype(att["k_arena"].dtype))
                        upd["v_arena"] = att["v_arena"].at[ids].set(
                            seg_v.astype(att["v_arena"].dtype))
                    out[name] = {"attention": upd}
                return (out, last_tok.at[slots].set(first_toks),
                        temps.at[slots].set(temperatures),
                        rngs.at[slots].set(slot_rngs))

            return paged_adopt

        @functools.partial(jax.jit, donate_argnums=(0, 4, 5, 6))
        def adopt(cache, small, slots, true_lens, last_tok, temps, rngs,
                  first_toks, temperatures, slot_rngs):
            """Splice prefill-cache rows ``0..n-1`` of ``small`` (padded to
            a group bucket — padding rows beyond n are ignored) into cache
            rows ``slots[0..n-1]`` and reset those cursors to the TRUE
            prompt lengths (bucket padding beyond them stays invisible and
            is overwritten by the next decode steps). Also installs each
            slot's sampling state. The group size n rides the arg shapes
            (jit retraces per size); the per-row dynamic_update_slice chain
            stays in place under donation — no full-cache pass."""
            return (_splice_rows(cache, small, slots, true_lens),
                    last_tok.at[slots].set(first_toks),
                    temps.at[slots].set(temperatures),
                    rngs.at[slots].set(slot_rngs))

        return adopt

    def build_draft_adopt(self):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def draft_adopt(dcache, small, slots, true_lens):
            """Splice draft-prefill rows into the (contiguous) draft cache
            — the sampling state lives with the target adopt; the draft
            only needs KV + cursors."""
            return _splice_rows(dcache, small, slots, true_lens)

        return draft_adopt

    def build_import(self):
        """Jitted KV-wire import (decode role): scatter one request's
        pre-filled blocks — [nb, block_t, h, d] per layer, plus the f32
        scale blocks when int8 — into the arena rows just granted to it,
        and install cursor/sampling state exactly as adoption would. One
        retrace per distinct block count (shape-keyed under jit), same as
        the prompt-bucketed adopt."""
        quant = self.kv_dtype == "int8"

        @functools.partial(jax.jit, donate_argnums=(0, 3, 4, 5))
        def import_kv(cache, wire, block_ids, last_tok, temps, rngs,
                      slot, true_len, first_tok, temperature, key):
            out = {}
            for name, layer in cache.items():
                att = layer["attention"]
                w = wire[name]
                upd = {
                    "k_arena": att["k_arena"].at[block_ids].set(
                        w["k"].astype(att["k_arena"].dtype)),
                    "v_arena": att["v_arena"].at[block_ids].set(
                        w["v"].astype(att["v_arena"].dtype)),
                    "cursors": att["cursors"].at[slot].set(true_len),
                }
                if quant:
                    upd["k_scale"] = att["k_scale"].at[block_ids].set(
                        w["k_scale"])
                    upd["v_scale"] = att["v_scale"].at[block_ids].set(
                        w["v_scale"])
                out[name] = {"attention": upd}
            return (out, last_tok.at[slot].set(first_tok),
                    temps.at[slot].set(temperature),
                    rngs.at[slot].set(key))

        return import_kv


def _splice_rows(cache, small, slots, true_lens):
    n = slots.shape[0]
    out = {}
    for name, layer in cache.items():
        att, small_att = layer["attention"], small[name]["attention"]
        k, v = att["k"], att["v"]
        for i in range(n):
            k = jax.lax.dynamic_update_slice(
                k, small_att["k"][i:i + 1], (slots[i], 0, 0, 0))
            v = jax.lax.dynamic_update_slice(
                v, small_att["v"][i:i + 1], (slots[i], 0, 0, 0))
        cursors = att["cursors"].at[slots].set(true_lens)
        out[name] = {"attention": {"k": k, "v": v, "cursors": cursors}}
    return out


class MimoFamily(_TokenAStep):
    """``models/mimo.py`` in the engine: two kinds of paged cache, prompts
    prefilled chunk by chunk straight into the arenas, expert counters."""

    has_stats = True
    prefills_in_arena = True
    kv_stride = 1

    def __init__(self, cfg: MimoConfig, *, slots: int, paged: bool = True,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        _two_kinds_only(paged, kv_dtype)
        self.cfg, self.slots, self.window = cfg, slots, int(cfg.window)
        self.kv_blocks = kv_blocks or slots * (cfg.max_seq // kv_block_t)
        self.kv_block_t = kv_block_t

    def rings(self, lookahead: int, engine_id: str = "0") -> WindowRings:
        """The window kind's accounting, a whole ring for every slot, for
        dispatches that move a cursor by up to ``lookahead`` positions. Its
        arena's size is the one number of the cache that the engine's knobs
        do not give: the cache and the step are built after this."""
        made = WindowRings(self.slots, self.window, self.kv_block_t, lookahead,
                           engine_id=engine_id)
        self.window_blocks = made.alloc.n_blocks
        self.trash = {FULL: self.kv_blocks, WINDOW: self.window_blocks}
        return made

    def routed(self, tokens: int) -> int:
        """Token-to-expert assignments of ``tokens`` tokens, all layers."""
        return tokens * self.cfg.experts_per_token * sum(self.cfg.moe_layers)

    def account(self, stats, tokens: int) -> Dict[str, int]:
        """An event's three counters into the program's own and the
        ``serving.engine.deliver`` region's stats: the assignments of the
        event's ``tokens`` live tokens (a decode chunk's rows, or the prompt
        a first token closes) that landed on the experts held here, the
        busiest held expert's, and how many held experts saw a token
        (summed over layers and steps)."""
        on_held, busiest, touched = stats
        METRICS.counter("serving_moe_assignments_total", held="true").inc(on_held)
        METRICS.counter("serving_moe_assignments_total", held="false").inc(
            max(self.routed(tokens) - on_held, 0))
        return {"expert_tokens": on_held, "expert_tokens_max": busiest,
                "experts_touched": touched}

    def fresh_cache(self) -> Dict[str, Any]:
        return mimo.fresh_cache(self.cfg, self.slots,
                                {FULL: self.kv_blocks, WINDOW: self.window_blocks},
                                self.kv_block_t)

    def build_step(self, chunk: int):
        return _build_counted_step(
            functools.partial(mimo.decode_step, self.cfg, trash=self.trash), chunk)

    def build_chunk_prefill(self):
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                          read_full, write_full, read_window, write_window):
            logits, cache, stats = mimo.prefill_chunk(
                cfg, params, cache, ids, start, n_valid,
                read_full, write_full, read_window, write_window)
            return cache, _sample_first(logits, temperature, key), stats

        return prefill_chunk

    def build_activate(self):
        return _build_activate()


def _build_counted_step(decode_step, chunk: int):
    """The decode program of a family whose step returns three counters
    beside the logits: ``chunk`` tokens for every slot, like the GPT
    family's step, and the counters of the whole chunk.
    ``decode_step(params, cache, tok, table, ring_table, live)``."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
    def step(params, cache, tok, temps, rngs, table, ring_table, live):
        def one(carry, _):
            cache, tok, rngs, stats = carry
            logits, cache, st = decode_step(params, cache, tok, table, ring_table, live)
            with jax.named_scope("sample"):
                nxt, rngs = sample_next(logits, temps, rngs)
            return (cache, nxt, rngs, stats + st), nxt

        (cache, tok, rngs, stats), toks = jax.lax.scan(
            one, (cache, tok, rngs, jnp.zeros((3,), jnp.int32)), None, length=chunk)
        return cache, tok, rngs, jnp.moveaxis(toks, 0, 1), stats

    return step


def _two_kinds_only(paged: bool, kv_dtype: str) -> None:
    if not paged:
        raise ValueError("this model family keeps two kinds of cache: "
                         "it needs the paged layout (paged=True)")
    if kv_dtype != "bf16":
        raise ValueError("this model family has no int8 arenas yet")


def _sample_first(logits, temperature, key):
    """The token a prompt's last position yields (only the LAST chunk's is
    read)."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temperature, 1e-6)).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _build_activate():
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def activate(cache, last_tok, temps, rngs, slot, true_len, first_tok,
                 temperature, key):
        """A prefilled row joins the decode batch: its cursor, its
        first token and its sampling state."""
        cache = dict(cache, cursors=cache["cursors"].at[slot].set(true_len))
        return (cache, last_tok.at[slot].set(first_tok),
                temps.at[slot].set(temperature), rngs.at[slot].set(key))

    return activate


class EvaFamily(_TokenAStep):
    """``models/evabyte.py`` in the engine: the local and the summary kind
    of paged cache, prompts prefilled chunk by chunk straight into the
    arenas, a count of the summaries written riding home with the tokens."""

    has_stats = True
    prefills_in_arena = True

    def __init__(self, cfg: EvaConfig, *, slots: int, paged: bool = True,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        _two_kinds_only(paged, kv_dtype)
        self.cfg, self.slots, self.kv_block_t = cfg, slots, kv_block_t
        #: a row of the block table is a chunk's summary
        self.kv_stride = int(cfg.chunk_size)
        self.kv_blocks = kv_blocks or slots * (
            cfg.max_seq // (kv_block_t * self.kv_stride))

    def rings(self, lookahead: int, engine_id: str = "0") -> AlignedWindows:
        """The local kind's accounting, a whole ring for every slot (its
        arena's size is not the engine's knob: the cache and the step are
        built after this)."""
        made = AlignedWindows(self.slots, self.cfg.window, self.kv_block_t,
                              lookahead, engine_id=engine_id)
        self.local_blocks = made.alloc.n_blocks
        return made

    def account(self, stats, tokens: int) -> Dict[str, int]:
        """An event's counters: the summaries its live rows (or the prompt
        a first token closes) wrote, and the windows they completed."""
        written, windows, _ = stats
        METRICS.counter("serving_eva_windows_summarised_total").inc(windows)
        return {"summaries_written": written}

    def fresh_cache(self) -> Dict[str, Any]:
        return evabyte.fresh_cache(self.cfg, self.slots, self.local_blocks,
                                   self.kv_blocks, self.kv_block_t)

    def build_step(self, chunk: int):
        return _build_counted_step(
            functools.partial(evabyte.decode_step, self.cfg, summary_trash=self.kv_blocks),
            chunk)

    def build_chunk_prefill(self):
        cfg, trash = self.cfg, self.kv_blocks

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                          read_summary, read_local, write_local):
            logits, cache, stats = evabyte.prefill_chunk(
                cfg, params, cache, ids, start, n_valid, read_summary, read_local,
                write_local, trash)
            return cache, _sample_first(logits, temperature, key), stats

        return prefill_chunk

    def build_activate(self):
        return _build_activate()


class SdarFamily:
    """``models/sdar.py`` in the engine: one kind of paged cache, prompts
    prefilled chunk by chunk straight into the arena, and a decode program
    of ``chunk`` forward passes in which every slot works on a block of its
    own: it reveals, or it commits and starts the next."""

    has_stats = True
    prefills_in_arena = True
    prefill_yields_token = False
    kv_stride = 1

    def __init__(self, cfg: SdarConfig, *, slots: int, paged: bool = True,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        if not paged or kv_dtype != "bf16":
            raise ValueError("this model family prefills into a paged bf16 arena "
                             "(paged=True, kv_dtype='bf16')")
        self.cfg, self.slots, self.kv_block_t = cfg, slots, kv_block_t
        self.kv_blocks = kv_blocks or slots * (cfg.max_seq // kv_block_t)
        #: the block in flight lies past the cursor
        self.kv_ahead = int(cfg.block_len)

    def rings(self, lookahead: int, engine_id: str = "0") -> None:
        """One kind of cache: no ring beside the block table."""
        return None

    def states(self) -> None:
        """No state a slot beside the blocks."""
        return None

    def cursor_moves(self, chunk: int) -> int:
        """A block takes a denoising pass and a commit at least: of
        ``chunk`` passes at most every other one commits."""
        return -(-chunk // 2) * self.cfg.block_len

    def account(self, stats, tokens: int) -> Dict[str, int]:
        """An event's counters (``models/sdar.STATS``) into the program's
        own and the ``serving.engine.deliver`` region's stats."""
        (on_held, busiest, touched, denoise, commit, blocks, revealed, pages,
         drawn) = stats
        METRICS.counter("serving_moe_assignments_total", held="true").inc(on_held)
        METRICS.counter("serving_block_forwards_total", kind="denoise").inc(denoise)
        METRICS.counter("serving_block_forwards_total", kind="commit").inc(commit)
        if denoise + commit:
            # a dispatch (a prefill chunk's event runs no pass): the slots'
            # temperatures are one for all its passes, so is the path
            METRICS.counter("serving_block_choice_dispatches_total",
                            path="materialised" if drawn else "streamed").inc()
        METRICS.counter("serving_blocks_committed_total").inc(blocks)
        METRICS.counter("serving_tokens_revealed_total").inc(revealed)
        return {"expert_tokens": on_held, "expert_tokens_max": busiest,
                "experts_touched": touched, "forwards": denoise + commit,
                "commit_forwards": commit, "blocks_committed": blocks,
                "revealed": revealed, "blocks_read": pages}

    def fresh_cache(self) -> Dict[str, Any]:
        return sdar.fresh_cache(self.cfg, self.slots, self.kv_blocks, self.kv_block_t)

    def build_step(self, chunk: int):
        """``chunk`` passes for every slot. Fetched: the tokens of the
        blocks each slot committed, first block's prompt tail left out
        ``[slots, width]``; how many ``[slots]``; the pass that revealed
        each; the cursors after the last pass; the counters."""
        cfg, trash = self.cfg, self.kv_blocks
        B = cfg.block_len
        width = self.cursor_moves(chunk)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
        def step(params, cache, tok, temps, rngs, table):
            S = tok.shape[0]
            put = jax.vmap(lambda row, new, at, do: jnp.where(
                do, jax.lax.dynamic_update_slice(row, new, (at,)), row))

            def one(carry, _):
                cache, rngs, toks, marks, n_out, starts, stats = carry
                pairs = jax.vmap(jax.random.split)(rngs)
                rngs, keys = pairs[:, 0], pairs[:, 1]
                cache, commit, ids, shown_at, skip, st = sdar.block_pass(
                    cfg, params, cache, table, temps, keys, trash)
                with jax.named_scope("hand_out"):
                    toks = put(toks, ids, n_out * B, commit)
                    marks = put(marks, shown_at, n_out * B, commit)
                    starts = jnp.where(commit & (n_out == 0), skip, starts)
                return (cache, rngs, toks, marks, n_out + commit, starts, stats + st), None

            zeros = jnp.zeros((S,), jnp.int32)
            (cache, rngs, toks, marks, n_out, starts, stats), _ = jax.lax.scan(
                one, (cache, rngs, jnp.zeros((S, width), jnp.int32),
                      jnp.zeros((S, width), jnp.int32), zeros, zeros,
                      jnp.zeros((sdar.STATS,), jnp.int32)), None, length=chunk)
            with jax.named_scope("hand_out"):
                # a slot's tokens from its row's first column on
                cols = jnp.mod(jnp.arange(width)[None, :] + starts[:, None], width)
                out = (jnp.take_along_axis(toks, cols, axis=1), n_out * B - starts,
                       jnp.take_along_axis(marks, cols, axis=1), cache["cursors"])
            return cache, tok, rngs, out, stats

        return step

    def build_chunk_prefill(self):
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                          read, write):
            opening, cache, stats = sdar.prefill_chunk(
                cfg, params, cache, ids, start, n_valid, read, write)
            return cache, opening, stats

        return prefill_chunk

    def build_activate(self):
        cfg = self.cfg
        B = cfg.block_len

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def activate(cache, last_tok, temps, rngs, slot, true_len, opening,
                     temperature, key):
            """A prefilled row joins the decode batch: its cursor behind the
            prompt's whole blocks, the block its tail opens, its sampling
            state."""
            tail = jnp.mod(true_len, B)
            cache = dict(
                cache, cursors=cache["cursors"].at[slot].set(true_len - tail),
                block_ids=cache["block_ids"].at[slot].set(opening),
                masked=cache["masked"].at[slot].set(jnp.arange(B) >= tail),
                revealed_at=cache["revealed_at"].at[slot].set(0),
                passes=cache["passes"].at[slot].set(0),
                skip=cache["skip"].at[slot].set(tail))
            return (cache, last_tok, temps.at[slot].set(temperature),
                    rngs.at[slot].set(key))

        return activate


class SarvamFamily(_TokenAStep):
    """``models/sarvam.py`` in the engine: one kind of paged cache, a
    latent row a position, prompts prefilled chunk by chunk straight into
    the arena (expanded), one token a slot and step (absorbed), and the
    expert and latent-page counters riding home with the tokens."""

    has_stats = True
    prefills_in_arena = True
    kv_stride = 1

    def __init__(self, cfg: SarvamConfig, *, slots: int, paged: bool = True,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        if not paged or kv_dtype != "bf16":
            raise ValueError("this model family prefills into a paged bf16 arena "
                             "(paged=True, kv_dtype='bf16')")
        self.cfg, self.slots, self.kv_block_t = cfg, slots, kv_block_t
        self.kv_blocks = kv_blocks or slots * (cfg.max_seq // kv_block_t)

    def rings(self, lookahead: int, engine_id: str = "0") -> None:
        """One kind of cache: no ring beside the block table."""
        return None

    def account(self, stats, tokens: int) -> Dict[str, int]:
        """An event's counters (``models/sarvam.STATS``) into the program's
        own and the ``serving.engine.deliver`` region's stats: as the MiMo
        family's, and the latent pages the decode attention fetched, a
        layer (0 for a prefill chunk's event)."""
        on_held, busiest, touched, pages = stats
        routed = tokens * self.cfg.experts_per_token * sum(self.cfg.moe_layers)
        METRICS.counter("serving_moe_assignments_total", held="true").inc(on_held)
        METRICS.counter("serving_moe_assignments_total", held="false").inc(
            max(routed - on_held, 0))
        METRICS.counter("serving_latent_pages_read_total").inc(pages)
        return {"expert_tokens": on_held, "expert_tokens_max": busiest,
                "experts_touched": touched, "latent_pages_read": pages}

    def fresh_cache(self) -> Dict[str, Any]:
        return sarvam.fresh_cache(self.cfg, self.slots, self.kv_blocks, self.kv_block_t)

    def build_step(self, chunk: int):
        """``chunk`` tokens for every slot, and the counters of the whole
        chunk. A dispatch takes the block table's first columns alone."""
        cfg, trash = self.cfg, self.kv_blocks

        @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
        def step(params, cache, tok, temps, rngs, table):
            def one(carry, _):
                cache, tok, rngs, stats = carry
                logits, cache, st = sarvam.decode_step(cfg, params, cache, tok, table, trash)
                with jax.named_scope("sample"):
                    nxt, rngs = sample_next(logits, temps, rngs)
                return (cache, nxt, rngs, stats + st), nxt

            (cache, tok, rngs, stats), toks = jax.lax.scan(
                one, (cache, tok, rngs, jnp.zeros((sarvam.STATS,), jnp.int32)), None,
                length=chunk)
            return cache, tok, rngs, jnp.moveaxis(toks, 0, 1), stats

        return step

    def build_chunk_prefill(self):
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                          read, write):
            logits, cache, stats = sarvam.prefill_chunk(
                cfg, params, cache, ids, start, n_valid, read, write)
            return cache, _sample_first(logits, temperature, key), stats

        return prefill_chunk

    def build_activate(self):
        return _build_activate()


class NemotronFamily(_TokenAStep):
    """``models/nemotron.py`` in the engine: the attention layers' paged
    arenas (the block table alone) and the Mamba layers' states, one a slot
    (``paged.SlotStates``); prompts prefilled chunk by chunk straight into
    both, one token a slot and step, the expert and state counters riding
    home with the tokens."""

    has_stats = True
    prefills_in_arena = True
    kv_stride = 1

    def __init__(self, cfg: NemotronConfig, *, slots: int, paged: bool = True,
                 kv_blocks: int = 0, kv_block_t: int = 16,
                 kv_dtype: str = "bf16"):
        if not paged or kv_dtype != "bf16":
            raise ValueError("this model family prefills into a paged bf16 arena "
                             "(paged=True, kv_dtype='bf16')")
        self.cfg, self.slots, self.kv_block_t = cfg, slots, kv_block_t
        self.kv_blocks = kv_blocks or slots * (cfg.max_seq // kv_block_t)

    def rings(self, lookahead: int, engine_id: str = "0") -> None:
        """No ring beside the block table."""
        return None

    def states(self) -> SlotStates:
        """A state a slot beside the block table."""
        return SlotStates(self.slots)

    def account(self, stats, tokens: int) -> Dict[str, int]:
        """An event's counters (``models/nemotron.STATS``) into the
        program's own and the ``serving.engine.deliver`` region's stats: as
        the MiMo family's, the Mamba states the event's steps or chunks read
        and wrote (``ssm_rows``, summed over the layers), the prompts whose
        first chunk zeroed their slot's states, and the KV pages the decode
        attention fetched (``kv_pages_read``, a layer; 0 for a prefill
        chunk's event)."""
        on_held, busiest, touched, rows, resets, pages = stats
        routed = tokens * self.cfg.experts_per_token * self.cfg.pattern.count(nemotron.EXPERTS)
        METRICS.counter("serving_moe_assignments_total", held="true").inc(on_held)
        METRICS.counter("serving_moe_assignments_total", held="false").inc(
            max(routed - on_held, 0))
        METRICS.counter("serving_ssm_state_rows_total").inc(rows)
        METRICS.counter("serving_ssm_states_reset_total").inc(resets)
        return {"expert_tokens": on_held, "expert_tokens_max": busiest,
                "experts_touched": touched, "ssm_rows": rows, "kv_pages_read": pages}

    def fresh_cache(self) -> Dict[str, Any]:
        return nemotron.fresh_cache(self.cfg, self.slots, self.kv_blocks, self.kv_block_t)

    def build_step(self, chunk: int):
        """``chunk`` tokens for every slot, and the counters of the whole
        chunk. A dispatch takes the block table's first columns and which
        rows are live."""
        cfg, trash = self.cfg, self.kv_blocks

        @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
        def step(params, cache, tok, temps, rngs, table, live):
            def one(carry, _):
                cache, tok, rngs, stats = carry
                logits, cache, st = nemotron.decode_step(cfg, params, cache, tok, table,
                                                         live, trash)
                with jax.named_scope("sample"):
                    nxt, rngs = sample_next(logits, temps, rngs)
                return (cache, nxt, rngs, stats + st), nxt

            (cache, tok, rngs, stats), toks = jax.lax.scan(
                one, (cache, tok, rngs, jnp.zeros((nemotron.STATS,), jnp.int32)), None,
                length=chunk)
            return cache, tok, rngs, jnp.moveaxis(toks, 0, 1), stats

        return step

    def build_chunk_prefill(self):
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                          read, write, slot):
            logits, cache, stats = nemotron.prefill_chunk(
                cfg, params, cache, ids, start, n_valid, read, write, slot)
            return cache, _sample_first(logits, temperature, key), stats

        return prefill_chunk

    def build_activate(self):
        return _build_activate()


_FAMILIES: Tuple[Tuple[type, type], ...] = ((GptConfig, GptFamily),
                                            (MimoConfig, MimoFamily),
                                            (EvaConfig, EvaFamily),
                                            (SdarConfig, SdarFamily),
                                            (SarvamConfig, SarvamFamily),
                                            (NemotronConfig, NemotronFamily))


def family_for(cfg: Any, **geometry: Any):
    """The family of a configuration, by its type."""
    for cfg_type, family in _FAMILIES:
        if isinstance(cfg, cfg_type):
            return family(cfg, **geometry)
    raise TypeError(f"no serving family for a {type(cfg).__name__}")
