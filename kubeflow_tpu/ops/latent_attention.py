"""Latent attention (MLA) over a paged latent arena: the serving path's two
kernels for a model whose cache keeps ONE row a position (models/sarvam.py).

A position's row is ``[c | k_r | 0]``: the normalised latent ``c``
(``latent`` wide, 512 at the published widths), the rotary key ``k_r`` that
every head shares (64), and zeros up to whole lanes (640 = 5 lane tiles: a
576-wide row would lie padded to 640 in HBM and in VMEM all the same, so
the arena is made 640 wide and the bytes a page moves are the bytes it
holds, ``block_t x 640 x 2``). Head ``h``'s key is ``[c W_uk_h | k_r]`` and
its value ``c W_uv_h``; there is no key or value arena of its own.

:func:`latent_decode_attention` — the ABSORBED form, one query position a
slot. The caller folds each head's nope query through ``W_uk_h`` into latent
space, so a head's query is a row as wide as the arena's (``[q W_uk_h^T |
q_r | 0]``) and its scores are one product with the page as it lies; the
context stays in latent space (the probabilities times the page's first
``latent`` columns) until the caller maps it through ``W_uv_h``. One grid
step is one slot: it walks the row's OWN pages ``table[s, : ceil(lengths[s]
/ block_t)]`` a group at a time (one DMA a page, double-buffered), so
nothing beyond a row's length is fetched, and each page is fetched ONCE for
both products (``ops/paged_attention`` reads a key and a value arena apart:
handed one arena twice it would fetch every page twice). A row of length 0
costs a grid step and returns zeros.

:func:`latent_prefill_attention` — the EXPANDED form, a chunk of queries of
one prompt against the row's whole view. A block of ``block_k`` latent rows
is expanded, head by head, into keys ``[block_k, nope]`` and values
``[block_k, v]`` in VMEM, used by every query tile that sees it and
dropped: the expanded view of a whole row (64 heads x 32,768 positions x
320 x 2 B = 1.34 GB a layer) never exists. The grid is (heads, key blocks);
the chunk's running maximum, sum and context live in VMEM across the key
blocks of a head (online softmax, as ``chunk_attention``), and a key block
no query of the chunk reaches is neither fetched nor computed. Expanded,
attention costs ``nope + rope + v`` = 320 multiply-adds a key and head where
the absorbed form costs ``2 latent + rope`` = 1,088: at a chunk of 2,048
queries the expansion (``2 latent x (nope + v)`` a key and head, once a
chunk) is the cheaper side. A tile's scores are ONE product of the query
``[q_nope | q_rope | 0]`` with the block's ``[k | k_r | 0]``, and the
softmax scale rides in the multiply ``exp2`` takes anyway (``scale x
log2 e``): no pass over the scores is the scale's alone, and the query
keeps the rounding of the unscaled one (a query scaled before its rounding
to bfloat16 runs no faster on the chip, but rounds otherwise and moves
near-ties of the served tokens). Only the tiles that cross the causal
diagonal (some key of the block past some query of the tile) build and
apply the mask; every other tile that sees the block sees all of it. The
running maximum and sum are one lane wide: written broadcast over 128
lanes, as a tile's whole state, they cost a quarter of the kernel at tiles
of 256 x 512.

Scores, maxima and sums are float32; every product takes the arena's type,
the probabilities rounded to it before the value product.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

_NEG_BIG = -1e30
_LOG2E = 1.4426950408889634
_LANE = 128
#: pages a group: 512 positions of 16, 655 KB of 640-wide rows a buffer
PAGES_PER_GROUP = 32
#: key rows a prefill step expands, and query rows a tile scores at once
BLOCK_K, BLOCK_Q = 1024, 512


def row_width(latent: int, rope: int) -> int:
    """The arena's row: latent and rotary key, padded to whole lanes."""
    return -(-(latent + rope) // _LANE) * _LANE


# -- decode: absorbed, each slot over its own pages --------------------------------

def _decode_kernel(lengths_ref, table_ref,                 # prefetched scalars
                   q_ref, arena_hbm, o_ref, buf, sems,
                   *, scale: float, latent: int, pages: int, block_t: int):
    s = pl.program_id(0)
    length = lengths_ref[s]
    n_pages = (length + block_t - 1) // block_t
    n_groups = (n_pages + pages - 1) // pages
    span = pages * block_t
    heads = q_ref.shape[1]

    def copy(g, slot, i):
        blk = table_ref[s, g * pages + i]
        rows = pl.ds(pl.multiple_of(i * block_t, block_t), block_t)
        return pltpu.make_async_copy(arena_hbm.at[blk], buf.at[slot, rows], sems.at[slot])

    def each_page(g, slot, act):
        def one(i, _):
            act(copy(g, slot, i))
        jax.lax.fori_loop(0, jnp.minimum(pages, n_pages - g * pages), one, None)

    @pl.when(n_groups > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    def body(g, carry):
        m, l, acc = carry
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _next():
            each_page(g + 1, 1 - slot, lambda c: c.start())

        each_page(g, slot, lambda c: c.wait())
        page = buf[slot]                                              # [span, width]
        sc = jax.lax.dot_general(
            q_ref[0], page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale               # [heads, span]
        held = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) < length
        sc = jnp.where(held, sc, _NEG_BIG)
        # a group exists only where its first position is held: no row of
        # the scores is masked whole, and exp(_NEG_BIG - m) is 0
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        # what lies past the length (and what an earlier group left in the
        # buffer) takes no part in the value product, whatever bits it holds
        below = g * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0) < length
        c = jnp.where(below, page[:, :latent], jnp.zeros((), page.dtype))
        pv = jnp.dot(p.astype(page.dtype), c, preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1, keepdims=True), acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        0, n_groups, body,
        (jnp.full((heads, 1), _NEG_BIG, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, latent), jnp.float32)))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)                    # nothing held: zeros


def latent_decode_attention(q: jax.Array, arena: jax.Array, table: jax.Array,
                            lengths: jax.Array, *, scale: float, latent: int,
                            pages: int = PAGES_PER_GROUP,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q ``[slots, heads, width]`` (each head's absorbed query, as wide as a
    row: ``[q_nope W_uk_h^T | q_rope | 0]``); ``arena`` ``[blocks, block_t,
    width]``; ``table`` [slots, columns] int32 (arena block of each of a
    row's pages; columns past a row's pages are never read); ``lengths``
    [slots] int32. Returns float32 ``[slots, heads, latent]``: for every slot
    the softmax over its positions ``0 .. lengths[s] - 1`` of ``q . row *
    scale``, times the rows' first ``latent`` columns; zeros where the length
    is 0."""
    slots, heads, width = q.shape
    block_t = arena.shape[1]
    if width != arena.shape[2] or latent > width:
        raise ValueError(f"queries {q.shape} do not match rows {arena.shape}")
    pages = min(pages, table.shape[1])
    span = pages * block_t
    lengths = jnp.minimum(lengths.astype(jnp.int32), table.shape[1] * block_t)
    if interpret is None:
        interpret = _interpret_default()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, heads, latent), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, span, width), arena.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), latent=latent,
                          pages=pages, block_t=block_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(lengths, table.astype(jnp.int32), q, arena)


# -- prefill: expanded a key block at a time ----------------------------------------

def _tile_ranges(j, start, nq: int, bq: int, bk: int):
    """Of key block ``j``, the query tiles ``first .. inner - 1`` that cross
    the causal diagonal and ``inner .. nq - 1`` that see the whole block:
    ``first`` is the first tile whose last query reaches the block's first
    key, ``inner`` the first whose first query reaches its last."""
    first = jnp.maximum(j * bk - start, 0) // bq
    inner = jnp.minimum(jnp.maximum(-(-(j * bk + bk - 1 - start) // bq), first), nq)
    return first, inner


def _prefill_kernel(start_ref,                              # prefetched scalar
                    q_ref, view_ref, wuk_ref, wuv_ref, o_ref,
                    acc, m_scr, l_scr, *, scale: float, latent: int, block_q: int,
                    block_k: int, nk: int):
    j = pl.program_id(1)
    start = start_ref[0]
    chunk = q_ref.shape[1]
    nq = chunk // block_q

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    # some query of the chunk (positions start .. start + chunk - 1) sees
    # some key of the block
    @pl.when(j * block_k <= start + chunk - 1)
    def _body():
        rows = view_ref[...]                                          # [block_k, width]
        c = rows[:, :latent]
        k = jnp.dot(c, wuk_ref[0], preferred_element_type=jnp.float32).astype(rows.dtype)
        v = jnp.dot(c, wuv_ref[0], preferred_element_type=jnp.float32).astype(rows.dtype)
        key = jnp.concatenate([k, rows[:, latent:]], axis=1)         # [k | k_r | 0]
        first, inner = _tile_ranges(j, start, nq, block_q, block_k)

        def tile(i, masked: bool):
            at = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            sc = jax.lax.dot_general(q_ref[0, at], key, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if masked:
                k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
                q_pos = start + i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, 1), 0)
                sc = jnp.where(k_pos <= q_pos, sc, _NEG_BIG)
            m_prev, l_prev = m_scr[at], l_scr[at]
            m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            # exp((sc - m) x scale), the scores and their maxima unscaled
            p = jnp.exp2((sc - m_new) * (scale * _LOG2E))
            if masked:
                # a row that sees no key of the block takes nothing from it
                p = jnp.where(sc > 0.5 * _NEG_BIG, p, 0.0)
            alpha = jnp.exp2((m_prev - m_new) * (scale * _LOG2E))
            pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            acc[at, :] = acc[at, :] * alpha + pv
            m_scr[at] = m_new
            l_scr[at] = l_prev * alpha + p.sum(axis=-1, keepdims=True)

        jax.lax.fori_loop(first, inner, lambda i, _: tile(i, True), None)
        jax.lax.fori_loop(inner, nq, lambda i, _: tile(i, False), None)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def latent_prefill_attention(q: jax.Array, view: jax.Array, w_uk: jax.Array,
                             w_uv: jax.Array, start: jax.Array, *, scale: float,
                             block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                             interpret: Optional[bool] = None) -> jax.Array:
    """A chunk's queries at positions ``start .. start + chunk - 1`` against
    the latent rows of positions ``0 .. keys - 1``, causal. ``q`` ``[heads,
    chunk, nope + width - latent]``: each head's query as ``[q_nope | q_rope
    | 0]`` (the rotary query and zeros as the row's tail lies);
    ``view`` ``[keys, width]`` (the row's blocks in order: what lies past the
    chunk is never read); ``w_uk`` ``[heads, latent, nope]``, ``w_uv``
    ``[heads, latent, v]``. Returns ``[heads, chunk, v]`` in the queries'
    type: the softmax over keys ``j <= position`` of ``(q_nope . c_j W_uk_h +
    q_rope . k_r_j) * scale``, times ``c_j W_uv_h``.

    ``chunk`` must be a multiple of ``min(block_q, chunk)``; the keys are
    padded here to a multiple of the key block."""
    heads, chunk, qw = q.shape
    keys, width = view.shape
    latent, nope, dv = w_uk.shape[1], w_uk.shape[2], w_uv.shape[2]
    if qw != nope + width - latent or w_uk.shape[0] != heads:
        raise ValueError(f"queries {q.shape} do not match rows {view.shape} and "
                         f"expansions {w_uk.shape}")
    bq = min(block_q, chunk)
    if chunk % bq:
        raise ValueError(f"{chunk} query rows are not a multiple of {bq}")
    bk = min(block_k, -(-keys // _LANE) * _LANE)
    pad = -keys % bk
    if pad:
        view = jnp.pad(view, ((0, pad), (0, 0)))
    nk = (keys + pad) // bk
    start = jnp.reshape(start, (1,)).astype(jnp.int32)
    if interpret is None:
        interpret = _interpret_default()

    def key_block(h, j, start_ref):
        # past the chunk's last position: the last block it reaches again,
        # which is not fetched anew
        return (jnp.minimum(j, (start_ref[0] + chunk - 1) // bk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads, nk),
        in_specs=[
            pl.BlockSpec((1, chunk, qw), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((bk, width), key_block),
            pl.BlockSpec((1, latent, nope), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((1, latent, dv), lambda h, j, *_: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda h, j, *_: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((chunk, dv), jnp.float32),
                        pltpu.VMEM((chunk, 1), jnp.float32),
                        pltpu.VMEM((chunk, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), latent=latent, block_q=bq,
                          block_k=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, chunk, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_prefill_attention",
    )(start, q, view, w_uk, w_uv)
