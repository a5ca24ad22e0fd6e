"""KV-cache writes of the paged serving path, and int8 KV quantization.

The paged layout keeps one shared arena ``[N, block_t, H, D]`` a layer plus
a per-slot block table ``[S, MB]`` of arena row ids (``serving/paged.py``
owns the host half). The LAST arena row (N-1) is the trash block: table
entries for unallocated positions point there, so a write through a trash
entry lands in a row nothing ever reads (the attention mask hides every
position at or beyond the row's cursor). That single convention is what
makes retirement safe without device synchronization: the owner of the
table redirects a slot's row to trash BEFORE returning its blocks to the
free list, and dispatches execute in issue order.

The write is one XLA scatter a token (:func:`kv_block_update`); the
contiguous per-slot cache writes with a where-select in ``models/gpt.py``.
No kernel stands beside them: the scope ``kv_write`` is 1.9% of a decode
chunk in the chat cell and 1.5% in the MiMo cell (PERF.md section 5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# int8 KV quantization — ISSUE 18.
#
# Symmetric per-(position-row, head) quantization: one f32 scale per written
# KV vector's head, computed as abs-max over head_dim / 127. The scale rides
# in a parallel arena shaped [N, block_t, H, 1] so the exact same block-table
# indirection (and the same scatter) addresses it. Zero-point is
# implicitly 0 (symmetric): rope'd keys and values are zero-mean enough that
# an asymmetric zero-point buys <0.1% extra SNR for 2x the bookkeeping.
# Everything is computed in f32 with round-half-even, so the device program
# and the host-side helper produce bit-identical int8 — the KV-handoff
# byte-parity contract depends on that.
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


def kv_block_update(arena: jax.Array, seg: jax.Array, cursors: jax.Array,
                    tables: jax.Array, *, max_seq: int) -> jax.Array:
    """Write ``seg[s, j]`` at ``arena[tables[s, p // block_t], p % block_t]``
    for ``p = cursors[s] + j``: one XLA scatter a token of the segment (a
    decode step writes one position a row, a speculative verify
    ``seg_len``).

    arena: [N, block_t, H, D]; seg: [S, L, H, D]; cursors: [S] (position of
    ``seg[:, 0]``); tables: [S, MB]. Out-of-range positions (at or beyond
    ``max_seq``, or beyond a table of which only the first columns were
    passed: a decode dispatch bounded to the granted blocks, where a row
    that steps past the table is one whose output nobody reads) are
    redirected to the trash row (N-1) instead of being skipped, so the
    whole update stays one scatter per token.
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
