"""Decode attention over a paged arena, each row over its OWN pages: the
serving path's kernel for one query position a slot (models/mimo.py, the
full-attention kind).

A decode step has one query position a slot and rows of very different
lengths in one batch (64 positions beside 8,000). Gathering every row's
view through the block table moves ``slots x widest view`` whatever the
rows hold; this kernel moves what is live. The arenas stay in HBM as they
lie (``[blocks + 1, block_t, kv_heads * width]``, a position's KV heads
side by side in one row) and are handed over untouched; the block table and
the rows' lengths ride as prefetched scalars. One grid step is one slot: it
walks the row's pages ``table[s, : ceil(lengths[s] / block_t)]`` a GROUP at
a time (``pages`` pages, one DMA a page and arena, double-buffered: group
``i + 1`` is in flight while group ``i`` is scored), so nothing is fetched
or computed beyond a row's length, and a row of length 0 (a dead slot, or
one still prefilling) costs a grid step and returns zeros.

Scores, the running maximum and the sums are float32 (online softmax over
the groups, as ``chunk_attention`` and ``flash_attention``); both products
take the arenas' type, the probabilities rounded to it before the value
product. The query heads are the matmul's rows. Keys of ``kv_heads`` heads
lie side by side in 192-wide columns that no lane tile respects, so the
caller hands the queries "heads apart" (``[heads, kv_heads * qk]``, a
head's vector in its KV head's columns and zeros elsewhere) and the score
product reads a row whole; values are sliced a KV head at a time.

Where every query head has a KV head of its own (``kv_heads == heads``:
models/evabyte.py) a head's slice of the probabilities would be ONE row, so
the value product is taken whole (``[heads, span] x [span, heads * v]``) and
each head keeps its own block of columns. A slot may bring more than one
query POSITION (models/sdar.py: a block of 4 positions x 32 heads): they
ride as more rows of the same matmul, a KV head's ``positions x group`` rows
together, and all of them read the row up to the one length, so the block
sees itself whole; nothing in the kernel knows a row's position. With
``stats=True`` the call also
returns the softmax's running maximum and sum, so that a caller with more
than one source of keys (a local window and a summary arena, each with a
table of its own) calls once a source and joins the results under ONE
softmax (:func:`join_softmax`).

The LATENT kind of arena (models/sarvam.py: one row a position that is key
and value at once, read in the absorbed form) is not this kernel's: handed
one arena as both, it would fetch every page twice. ``ops/latent_attention``
walks a slot's pages the same way and uses each page for both products.
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
_LANE = 128
#: pages a group: 512 positions of 16, 1.3 MB of keys and values a buffer
PAGES_PER_GROUP = 32


def _kernel(lengths_ref, table_ref,                     # prefetched scalars
            q_ref, k_hbm, v_hbm, o_ref, *rest,
            scale: float, kv_heads: int, pages: int, block_t: int):
    # with stats: two more outputs (the running maximum and sum) lead ``rest``
    *stat_refs, kbuf, vbuf, sems = rest
    s = pl.program_id(0)
    length = lengths_ref[s]
    n_pages = (length + block_t - 1) // block_t
    n_groups = (n_pages + pages - 1) // pages
    span = pages * block_t
    heads = q_ref.shape[1]
    group, dv = heads // kv_heads, vbuf.shape[-1] // kv_heads

    def copies(g, slot, i):
        blk = table_ref[s, g * pages + i]
        rows = pl.ds(pl.multiple_of(i * block_t, block_t), block_t)
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot, rows], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot, rows], sems.at[1, slot]))

    def each_page(g, slot, act):
        def one(i, _):
            for copy in copies(g, slot, i):
                act(copy)
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
        sc = jax.lax.dot_general(
            q_ref[0], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale             # [heads, span]
        held = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) < length
        sc = jnp.where(held, sc, _NEG_BIG)
        # a group exists only where its first position is held, so no row
        # of the scores is masked whole and exp(_NEG_BIG - m) is 0
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        # what lies past the length in the last page (and what an earlier
        # group left in the buffer) takes no part, whatever bits it holds
        below = g * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0) < length
        v = jnp.where(below, vbuf[slot], jnp.zeros((), vbuf.dtype))
        pb = p.astype(v.dtype)
        if group == 1:
            # a head's own slice of ``pb`` would be one row: the product
            # whole, and each head keeps its own block of columns
            whole = jnp.dot(pb, v, preferred_element_type=jnp.float32)
            head = jax.lax.broadcasted_iota(jnp.int32, (heads, dv), 0)
            pv = jnp.zeros((heads, dv), jnp.float32)
            for h in range(kv_heads):
                pv = pv + jnp.where(head == h, whole[:, h * dv:(h + 1) * dv], 0.0)
        else:
            pv = jnp.concatenate([
                jnp.dot(pb[h * group:(h + 1) * group], v[:, h * dv:(h + 1) * dv],
                        preferred_element_type=jnp.float32)
                for h in range(kv_heads)], axis=0)                  # [heads, dv]
        return m_new, l * alpha + p.sum(axis=-1, keepdims=True), acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        0, n_groups, body,
        (jnp.full((heads, 1), _NEG_BIG, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, dv), jnp.float32)))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)                    # nothing held: zeros
    if stat_refs:
        m_ref, l_ref = stat_refs
        m_ref[0] = jnp.broadcast_to(m, m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


def paged_decode_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                           table: jax.Array, lengths: jax.Array, *, scale: float,
                           kv_heads: int, pages: int = PAGES_PER_GROUP,
                           interpret: Optional[bool] = None, stats: bool = False):
    """q ``[slots, heads, kv_heads * qk]``, heads apart (head ``h`` holds its
    vector in the columns of KV head ``h // (heads / kv_heads)`` and zeros
    elsewhere); arenas ``[blocks, block_t, kv_heads * qk]`` and ``[blocks,
    block_t, kv_heads * v]``; ``table`` [slots, columns] int32 (arena block of
    each of a row's pages; columns past a row's pages are never read);
    ``lengths`` [slots] int32, cut to the table's span. Returns float32
    ``[slots, heads, v]``: for every slot the softmax over its positions
    ``0 .. lengths[s] - 1`` of ``q . k * scale``, times its own KV head's
    values; zeros where the length is 0. With ``stats``: ``(out, m, l)``, the
    softmax's maximum (``-1e30`` where the length is 0) and the sum of
    ``exp(score - m)``, float32 ``[slots, heads]``."""
    slots, heads, wide = q.shape
    block_t, dv = k_arena.shape[1], v_arena.shape[2] // kv_heads
    if wide != k_arena.shape[2] or heads % kv_heads:
        raise ValueError(f"queries {q.shape} do not match keys {k_arena.shape} "
                         f"of {kv_heads} heads")
    pages = min(pages, table.shape[1])
    span = pages * block_t
    lengths = jnp.minimum(lengths.astype(jnp.int32), table.shape[1] * block_t)
    if interpret is None:
        interpret = _interpret_default()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, wide), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((1, heads, dv), lambda s, *_: (s, 0, 0))]
        + [pl.BlockSpec((1, heads, _LANE), lambda s, *_: (s, 0, 0))] * (2 * stats),
        scratch_shapes=[pltpu.VMEM((2, span, wide), k_arena.dtype),
                        pltpu.VMEM((2, span, kv_heads * dv), v_arena.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    out, *ml = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), kv_heads=kv_heads,
                          pages=pages, block_t=block_t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, heads, dv), jnp.float32)]
        + [jax.ShapeDtypeStruct((slots, heads, _LANE), jnp.float32)] * (2 * stats),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths, table.astype(jnp.int32), q, k_arena, v_arena)
    if not stats:
        return out
    return out, ml[0][..., 0], ml[1][..., 0]


def join_softmax(*parts):
    """One softmax over several sources of keys: ``parts`` are ``(out, m,
    l)`` of :func:`paged_decode_attention` over the same queries, each
    normalised over its own source. Returns float32 ``[slots, heads, v]``,
    normalised over all of them; zeros where no source held anything."""
    top = functools.reduce(jnp.maximum, (m for _, m, _ in parts))
    mass = [l * jnp.exp(m - top) for _, m, l in parts]
    total = sum(mass)
    out = sum(o * w[..., None] for (o, _, _), w in zip(parts, mass))
    return out / jnp.where(total == 0.0, 1.0, total)[..., None]
