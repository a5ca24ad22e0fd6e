"""Grouped matrix products over rows sorted by group: the serving path's
kernel for the experts held on a chip (parallel/moe.py ``held_experts_ffn``).

``lhs`` [m, k] holds the rows of group 0, then of group 1, and so on
(``group_sizes`` int32 [groups] rows each); what lies past the last group
belongs to nobody. ``rhs`` [groups, k, n] holds a matrix a group. The
product of every row with its own group's matrix is bound by the matrices'
bytes wherever a group holds few rows (a decode step: 128 experts of 9.4 MB
for about 5 rows each), so the kernel is built around reading each matrix
ONCE: its grid walks the VISITS, the (row tile, group) pairs that hold a
row, in the order of the rows, and nothing else. A visit fetches the row
tile (kept from the visit before where that was the same tile) and the
group's matrix, multiplies the whole tile in the operands' type with
float32 accumulation, and keeps the rows that are the group's: the others
stay what an earlier visit of the tile made them, zeros on its first. A
group without rows is never visited and its matrix never read; a row tile
past the last group is never visited and never WRITTEN: those rows of the
result hold whatever the buffer held, and the caller masks them as it masks
the tail of a tile (``held_experts_ffn`` zeroes the rows past the groups).

The visits are counted on the device (``_visits``: at most ``row tiles +
groups - 1``) and ride with the groups' row offsets as one prefetched vector;
the grid's length is that count, so a batch of 500 rows in a buffer of
2,048 costs what 500 rows cost. Tiles follow the shapes alone: the
contraction is whole (an expert's ``d`` or ``f``: one reading of the row
tile a visit, no accumulator to carry), the matrices' columns are whole
where that fits ``_RHS_BLOCK_BYTES`` and else the largest 128-multiple
divisor that does, and the row tile is ``_ROW_TILE`` rows (at 128 a visit's
product hides behind the fetch of its matrix; on a v5e tiles of 256 were no
faster where groups hold 64-128 rows and 4% slower at 5 rows a group, tiles
of 512 half as fast: PERF.md section 6, PR 36). Dimensions under a tile, or
no multiple of one, are taken whole or ragged at the end, so the toy widths
of the tests run the same code, interpreted off the TPU.

``grouped_swiglu`` is the same walk over two stacks of matrices at once:
``silu(x @ gate) * (x @ up)`` in float32, rounded once to the result's
type, so that the row tile is read once and neither product leaves the
chip's fast memory.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

_LANE = 128
#: rows a tile: a visit multiplies the whole tile, so its product should not
#: outlast the fetch of its matrix (197 TFLOP/s over 819 GB/s = 240 rows)
_ROW_TILE = 128
#: bytes of one matrix block in fast memory (two stacks, two buffers each)
_RHS_BLOCK_BYTES = 4 << 20


def _visits(group_sizes: jax.Array, m: int, tm: int) -> jax.Array:
    """The (row tile, group) pairs that hold a row, in row order, as ONE
    int32 vector for the kernel's scalar memory: ``[count, row offsets of
    the groups (groups + 1), group of each visit, row tile of each visit]``,
    the last two ``row tiles + groups - 1`` long and read only below the
    count. Sums over comparisons, no scan and no gather: a handful of fused
    operations a layer (an expert layer's program is loaded from the compile
    cache at every start, and what it holds is what that costs)."""
    groups = group_sizes.shape[0]
    g = jnp.arange(groups, dtype=jnp.int32)
    upto_and_with = g[None, :] <= g[:, None]                  # [g, g']: g' <= g
    ends = jnp.sum(jnp.where(upto_and_with, group_sizes[None, :], 0), axis=1)
    first = (ends - group_sizes) // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.sum(jnp.where(upto_and_with, tiles[None, :], 0), axis=1)
    v = jnp.arange(pl.cdiv(m, tm) + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1), groups - 1)
    at = group[:, None] == g[None, :]                          # [v, g] one-hot
    tile = v + jnp.sum(jnp.where(at, (first - upto + tiles)[None, :], 0), axis=1)
    return jnp.concatenate([upto[-1:], jnp.zeros(1, jnp.int32), ends, group, tile]
                           ).astype(jnp.int32)


def _kernel(plan_ref, x_ref, *refs, tm: int, group_at: int, tile_at: int, relu2=False):
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    g, t = plan_ref[group_at + v], plan_ref[tile_at + v]
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= plan_ref[1 + g]) & (row < plan_ref[2 + g])
    opened = (v == 0) | (plan_ref[tile_at + jnp.maximum(v - 1, 0)] != t)
    x = x_ref[...]
    y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
    if len(w_refs) == 2:
        y = jax.nn.silu(y) * jnp.dot(x, w_refs[1][...], preferred_element_type=jnp.float32)
    y = jnp.square(jnp.maximum(y, 0.0)) if relu2 else y
    # the other groups' rows: what their visits made them, zeros before
    kept = jnp.where(opened, jnp.zeros((), o_ref.dtype), o_ref[...])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), kept)


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """All ``n`` columns where a [k, n] block fits, else the largest
    128-multiple divisor of ``n`` that does (128 where none does)."""
    if k * n * itemsize <= _RHS_BLOCK_BYTES or n % _LANE:
        return n
    fits = [tn for tn in range(_LANE, n, _LANE)
            if n % tn == 0 and k * tn * itemsize <= _RHS_BLOCK_BYTES]
    return max(fits, default=_LANE)


@functools.partial(jax.jit, static_argnames=("out_dtype", "name", "interpret", "relu2"))
def _call(lhs: jax.Array, stacks: Tuple[jax.Array, ...], group_sizes: jax.Array,
          out_dtype, name: str, interpret: bool, relu2: bool = False) -> jax.Array:
    m, k = lhs.shape
    groups, k_rhs, n = stacks[0].shape
    if k != k_rhs or any(w.shape != stacks[0].shape for w in stacks) \
            or group_sizes.shape != (groups,):
        raise ValueError(f"rows {lhs.shape} do not match matrices "
                         f"{[w.shape for w in stacks]} of {group_sizes.shape} groups")
    tm = min(m, _ROW_TILE)
    tn = _column_tile(k, n, stacks[0].dtype.itemsize)
    plan = _visits(group_sizes.astype(jnp.int32), m, tm)
    group_at = groups + 2                       # past the count and the offsets
    tile_at = group_at + (plan.shape[0] - group_at) // 2
    rhs_spec = pl.BlockSpec((None, k, tn), lambda j, v, plan: (plan[group_at + v], 0, j))
    block_bytes = (2 * len(stacks) * k * tn * stacks[0].dtype.itemsize
                   + 2 * tm * k * lhs.dtype.itemsize
                   + (2 + 2 * len(stacks)) * tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, group_at=group_at, tile_at=tile_at, relu2=relu2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, plan[0]),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, plan: (plan[tile_at + v], 0))]
            + [rhs_spec] * len(stacks),
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, plan: (plan[tile_at + v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=block_bytes + (8 << 20)),
        interpret=interpret,
        name=name,
    )(plan, lhs, *stacks)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   preferred_element_type=jnp.float32,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``lhs`` [m, k] sorted by group, ``rhs`` [groups, k, n], ``group_sizes``
    int32 [groups] with a sum of at most m. Returns [m, n] in
    ``preferred_element_type``: row r of group g is ``lhs[r] @ rhs[g]``
    (operands as they are, float32 accumulation); the rows past the last
    group are zeros or were never written."""
    return _call(lhs, (rhs,), group_sizes, preferred_element_type, "grouped_matmul",
                 _interpret_default() if interpret is None else interpret)


def grouped_swiglu(lhs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   group_sizes: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``silu(lhs[r] @ w_gate[g]) * (lhs[r] @ w_up[g])`` for every row r of
    group g, both products and the gating in float32, rounded once to
    ``lhs``'s type; shapes and the rows past the groups as
    :func:`grouped_matmul`. Returns [m, n] in ``lhs``'s type."""
    return _call(lhs, (w_gate, w_up), group_sizes, lhs.dtype, "grouped_swiglu",
                 _interpret_default() if interpret is None else interpret)


def grouped_relu2(lhs: jax.Array, w_up: jax.Array, group_sizes: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """``relu(lhs[r] @ w_up[g]) ** 2`` for every row r of group g (an expert
    with one matrix in and no gate: models/nemotron.py), the product and the
    square in float32, rounded once to ``lhs``'s type; shapes and the rows
    past the groups as :func:`grouped_matmul`. Returns [m, n] in ``lhs``'s
    type."""
    return _call(lhs, (w_up,), group_sizes, lhs.dtype, "grouped_relu2",
                 _interpret_default() if interpret is None else interpret, relu2=True)
