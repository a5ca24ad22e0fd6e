"""Per-row KV-cache row update — the continuous-batching write primitive.

Slot-based decode (serving/continuous.py) keeps one KV cache of shape
[slots, max_seq, heads, head_dim] with an independent cursor per row. Each
decode step must write ONE [heads, head_dim] vector per row at that row's
cursor. The pure-XLA formulations all touch the whole cache per layer:

- ``jnp.where(position == cursor, new, cache)`` — one full read+write
  elementwise pass over the cache (round-4 measured: turns the 3.3 ms
  shared-cursor decode step into 8.2 ms at 24 layers);
- vmapped ``dynamic_update_slice`` / ``.at[arange, cursors].set`` — lower
  to scatter, measured ~3x slower still (models/gpt.py:164-167).

This kernel touches only the [1, block_t, heads, head_dim] tile containing
each row's cursor: grid over slots, the cursor scalars are prefetched so
the block index map can select the tile, and ``input_output_aliases``
makes the update in place (no fresh cache buffer, no full-cache pass).
Per step it moves S*block_t*h*d elements instead of S*max_seq*h*d — for
the serving bench shapes that is 44x less cache traffic per layer.

The kernel's case does not rest on Pallas streaming HBM as fast as XLA
does: it removes the stream entirely instead of re-emitting it.

No reference analog: the reference (equinor/kubeflow) contains no serving
kernels; this is TPU-first infrastructure for the crud-web-app-adjacent
serving path (SURVEY.md section 2.9/2.10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(cur_ref, cache_ref, new_ref, out_ref, *, block_t: int, t: int):
    s = pl.program_id(0)
    cur = cur_ref[s]
    off = jnp.minimum(cur, t - 1) % block_t
    out_ref[...] = cache_ref[...]
    # Out-of-range cursors (retired/idle rows stepping past their end) must
    # be a NO-OP, matching the where-select path where no position compares
    # equal — not a write that corrupts the last KV position.
    out_ref[0, pl.dslice(off, 1)] = jnp.where(
        cur < t, new_ref[0], cache_ref[0, pl.dslice(off, 1)])


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def kv_row_update(cache: jax.Array, new: jax.Array, cursors: jax.Array,
                  *, block_t: int = 8, interpret: bool | None = None) -> jax.Array:
    """Return ``cache`` with ``new[s]`` written at ``cache[s, cursors[s]]``.

    cache: [S, T, H, D]; new: [S, H, D] (or [S, 1, H, D]); cursors: [S] int32.
    In place when the caller donates ``cache`` (the serving engine's step
    donates the whole cache pytree). Cursors at or beyond T are a NO-OP for
    that row: the engine lets retired/idle rows keep stepping past their
    end (static shapes — every row computes every chunk), and the
    where-select path writes nothing there (no position compares equal), so
    the kernel must agree rather than rewrite position T-1. The block index
    still clamps to the last tile to avoid out-of-bounds tile selection;
    the in-kernel predicate keeps the data untouched.
    """
    S, T, H, D = cache.shape
    if new.ndim == 3:
        new = new[:, None]
    if T % block_t != 0:
        # largest divisor of T not above the requested tile
        block_t = next(b for b in range(min(block_t, T), 0, -1) if T % b == 0)
    if interpret is None:
        interpret = _interpret_default()

    def cache_block(s, cur):
        return (s, jnp.minimum(cur[s], T - 1) // block_t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, block_t, H, D), cache_block),
            pl.BlockSpec((1, 1, H, D), lambda s, cur: (s, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, H, D), cache_block),
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, t=T),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        grid_spec=grid_spec,
        input_output_aliases={1: 0},  # flattened args: (cursors, cache, new)
        interpret=interpret,
    )(cursors.astype(jnp.int32), cache, new.astype(cache.dtype))


# ---------------------------------------------------------------------------
# Paged (block-table) variants — ISSUE 12.
#
# The paged layout replaces the per-slot [S, T, H, D] cache with one shared
# arena [N, block_t, H, D] plus a per-slot block table [S, MB] of arena row
# ids. The LAST arena row (N-1) is the trash block: table entries for
# unallocated positions point there, so a write through a trash entry lands
# in a row nothing ever reads (the attention mask hides every position at or
# beyond the row's cursor). That single convention is what makes retirement
# safe without device synchronization: the engine redirects a slot's table
# row to trash BEFORE returning its blocks to the free list, and dispatches
# execute in issue order.
# ---------------------------------------------------------------------------


def _arena_block_map(block_t: int, max_seq: int, mb: int, trash: int):
    """Index map of the paged kernels: grid step ``s`` -> the arena tile
    that holds row ``s``'s cursor, chased through the prefetched table;
    the trash tile for a cursor beyond the table's ``mb`` columns."""
    def arena_block(s, cur, tbl):
        col = jnp.minimum(cur[s], max_seq - 1) // block_t
        return (jnp.where(col < mb, tbl[s, jnp.minimum(col, mb - 1)], trash),
                0, 0, 0)
    return arena_block


def _paged_kernel(cur_ref, tbl_ref, arena_ref, new_ref, out_ref,
                  *, block_t: int, max_seq: int):
    s = pl.program_id(0)
    cur = cur_ref[s]
    off = jnp.minimum(cur, max_seq - 1) % block_t
    out_ref[...] = arena_ref[...]
    # Same no-op contract as kv_row_update: a cursor at or beyond max_seq
    # leaves the tile untouched (the index map still selects a valid tile).
    out_ref[0, pl.dslice(off, 1)] = jnp.where(
        cur < max_seq, new_ref[0], arena_ref[0, pl.dslice(off, 1)])


@functools.partial(jax.jit, static_argnames=("max_seq", "interpret"))
def kv_block_update(arena: jax.Array, new: jax.Array, cursors: jax.Array,
                    tables: jax.Array, *, max_seq: int,
                    interpret: bool | None = None) -> jax.Array:
    """Paged generalization of :func:`kv_row_update`.

    arena: [N, block_t, H, D] shared block arena (row N-1 is the trash
    block); new: [S, H, D] (or [S, 1, H, D]); cursors: [S] int32 absolute
    positions; tables: [S, MB] int32 arena row ids per slot.

    Writes ``new[s]`` at ``arena[tables[s, cursors[s] // block_t],
    cursors[s] % block_t]``. Both the cursor- and table-scalars are
    prefetched so the block index map can chase the indirection; the grid
    stays (S,) and each step touches exactly one [1, block_t, H, D] tile.
    Cursors at or beyond ``max_seq`` are a no-op for the data (the tile
    selection clamps, the in-kernel predicate skips the write); positions
    whose table entry is the trash block land in the trash row, and so do
    positions beyond the table where the caller passes only its first
    columns (a decode dispatch bounded to the granted blocks: a row that
    steps past the table is one whose output nobody reads).
    """
    N, block_t, H, D = arena.shape
    S = new.shape[0]
    mb = tables.shape[1]
    if new.ndim == 3:
        new = new[:, None]
    if interpret is None:
        interpret = _interpret_default()

    arena_block = _arena_block_map(block_t, max_seq, mb, trash=N - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, block_t, H, D), arena_block),
            pl.BlockSpec((1, 1, H, D), lambda s, cur, tbl: (s, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, H, D), arena_block),
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, block_t=block_t, max_seq=max_seq),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        grid_spec=grid_spec,
        # flattened args: (cursors, tables, arena, new)
        input_output_aliases={2: 0},
        interpret=interpret,
    )(cursors.astype(jnp.int32), tables.astype(jnp.int32),
      arena, new.astype(arena.dtype))


# ---------------------------------------------------------------------------
# int8 KV quantization — ISSUE 18.
#
# Symmetric per-(position-row, head) quantization: one f32 scale per written
# KV vector's head, computed as abs-max over head_dim / 127. The scale rides
# in a parallel arena shaped [N, block_t, H, 1] so the exact same block-table
# indirection (and the same scatter reference) addresses it. Zero-point is
# implicitly 0 (symmetric): rope'd keys and values are zero-mean enough that
# an asymmetric zero-point buys <0.1% extra SNR for 2x the bookkeeping.
# Everything is computed in f32 with round-half-even, so the Pallas kernel,
# the XLA reference, and the host-side helper produce bit-identical int8 —
# the KV-handoff byte-parity contract depends on that.
# ---------------------------------------------------------------------------


def quantize_kv(x: jax.Array):
    """Quantize KV vectors symmetrically per head row.

    x: [..., H, D] (bf16/f32) -> (int8 [..., H, D], f32 scales [..., H, 1]).
    ``dequantize_kv(q, s)`` recovers x to within scale/2 per element. All-zero
    rows quantize to zeros with scale 0 (dequant is exactly 0).
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.where(scale > 0, scale, 1.0)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv` (f32 out)."""
    return q.astype(jnp.float32) * scale


# Compiled quantizer shared by the adoption path and the KV-wire exporter.
# Eager and jitted quantize_kv disagree by ~1 ULP in scale (XLA rewrites the
# division to a reciprocal multiply), which flips int8 codes at rounding
# boundaries — jit-vs-jit is bit-identical across batch shapes, so every
# producer of arena bytes must go through this one entry point for the
# moved-vs-never-moved parity contract to hold.
quantize_kv_jit = jax.jit(quantize_kv)


def _paged_quant_kernel(cur_ref, tbl_ref, arena_ref, scale_ref, new_ref,
                        q_out_ref, s_out_ref, *, block_t: int, max_seq: int):
    s = pl.program_id(0)
    cur = cur_ref[s]
    off = jnp.minimum(cur, max_seq - 1) % block_t
    q_out_ref[...] = arena_ref[...]
    s_out_ref[...] = scale_ref[...]
    x = new_ref[0].astype(jnp.float32)                       # [1, H, D]
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)),
                 -127, 127).astype(jnp.int8)
    write = cur < max_seq
    q_out_ref[0, pl.dslice(off, 1)] = jnp.where(
        write, q, arena_ref[0, pl.dslice(off, 1)])
    s_out_ref[0, pl.dslice(off, 1)] = jnp.where(
        write, scale, scale_ref[0, pl.dslice(off, 1)])


@functools.partial(jax.jit, static_argnames=("max_seq", "interpret"))
def kv_block_update_quant(arena: jax.Array, scales: jax.Array, new: jax.Array,
                          cursors: jax.Array, tables: jax.Array, *,
                          max_seq: int, interpret: bool | None = None):
    """Store-quantized variant of :func:`kv_block_update`.

    arena: [N, block_t, H, D] int8; scales: [N, block_t, H, 1] f32; new:
    [S, H, D] (or [S, 1, H, D]) bf16/f32. Quantizes ``new`` INSIDE the
    kernel (same math as :func:`quantize_kv`) and writes value + scale
    through the block table in one pass — both arenas alias in place. Same
    out-of-range and beyond-the-table contract as the bf16 kernel.
    """
    N, block_t, H, D = arena.shape
    S = new.shape[0]
    mb = tables.shape[1]
    if new.ndim == 3:
        new = new[:, None]
    if interpret is None:
        interpret = _interpret_default()

    arena_block = _arena_block_map(block_t, max_seq, mb, trash=N - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, block_t, H, D), arena_block),
            pl.BlockSpec((1, block_t, H, 1), arena_block),
            pl.BlockSpec((1, 1, H, D), lambda s, cur, tbl: (s, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, H, D), arena_block),
            pl.BlockSpec((1, block_t, H, 1), arena_block),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_quant_kernel, block_t=block_t,
                          max_seq=max_seq),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, jnp.int8),
                   jax.ShapeDtypeStruct(scales.shape, jnp.float32)],
        grid_spec=grid_spec,
        # flattened args: (cursors, tables, arena, scales, new)
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(cursors.astype(jnp.int32), tables.astype(jnp.int32),
      arena, scales, new)


def kv_block_update_ref(arena: jax.Array, seg: jax.Array, cursors: jax.Array,
                        tables: jax.Array, *, max_seq: int) -> jax.Array:
    """XLA scatter reference for :func:`kv_block_update`, generalized to
    multi-token segments (speculative-verify writes ``seg_len`` positions
    per row in one call).

    arena: [N, block_t, H, D]; seg: [S, L, H, D]; cursors: [S] (position of
    ``seg[:, 0]``); tables: [S, MB]. Out-of-range positions (at or beyond
    ``max_seq``, or beyond a table of which only the first columns were
    passed) are redirected to the trash row (N-1) instead of being skipped
    so the whole update stays one scatter per token.
    """
    N, block_t, _, _ = arena.shape
    S, L = seg.shape[:2]
    mb = tables.shape[1]
    rows = jnp.arange(S)
    cursors = cursors.astype(jnp.int32)
    for j in range(L):
        pos = cursors + j
        bi = pos // block_t
        blk = jnp.where((pos < max_seq) & (bi < mb),
                        tables[rows, jnp.clip(bi, 0, mb - 1)], N - 1)
        arena = arena.at[blk, pos % block_t].set(seg[:, j].astype(arena.dtype))
    return arena
