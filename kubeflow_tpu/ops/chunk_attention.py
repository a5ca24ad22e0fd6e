"""Fused attention of a prefill chunk against keys that carry their own
positions: the serving path's kernel for models whose layers differ in
kind (models/mimo.py).

One kernel serves a full-attention layer (the chunk's queries against the
row's whole paged view) and a sliding-window layer (against what is left
of the earlier chunks in the ring, and the chunk itself), because the mask
is computed from POSITIONS, not from where a key happens to sit:

    valid(query r, key j)  <=>  0 <= q_pos[r] - k_pos[j] < window

so keys may come in any order (a ring's columns), and a key that holds
nothing is given a position no query reaches. Grouped queries ride as ROWS:
``q`` is ``[kv_heads, rows, qk]`` with the ``group`` query heads of a KV
head laid out as consecutive rows of one position, so each key / value tile
is fetched once a KV head and the score matmul has ``block_q`` rows
whatever the group size. Key and value widths may differ (``qk`` 192,
``v`` 128). A learnable ``sink`` per row joins the softmax's denominator
and takes no value. Scores, the running maximum and the sums are float32
(online softmax, as ``flash_attention``); the two matmuls take the
operands' type.

Blocks no query of the tile can see are skipped from bounds of the
positions that the wrapper computes and the kernel reads as prefetched
scalars: a chunk late in a long prompt pays for the keys before it, not for
the view's unwritten tail, and a window layer for its band.
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
#: a key that holds nothing: no query's position reaches it
NOWHERE = 1 << 30


def _kernel(q_lo, q_hi, k_lo, k_hi,                     # prefetched scalars
            q_ref, k_ref, v_ref, qpos_ref, kpos_ref, sink_ref, o_ref,
            acc, m_scr, l_scr, *, scale: float, window: int, nk: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    # some query of the tile sees some key of the block
    @pl.when((q_hi[iq] >= k_lo[ik]) & (q_lo[iq] - k_hi[ik] < window))
    def _body():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        gap = qpos_ref[:] - kpos_ref[:]                 # [bq, 1] - [1, bk]
        s = jnp.where((gap >= 0) & (gap < window), s, _NEG_BIG)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # a row with every entry masked has m_new == _NEG_BIG and exp(0) == 1
        p = jnp.where(s > 0.5 * _NEG_BIG, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_prev * alpha + p.sum(axis=-1)

    @pl.when(ik == nk - 1)
    def _finish():
        m, l, sink = m_scr[:, 0], l_scr[:, 0], sink_ref[0, :, 0]
        # the sink's exponential joins the denominator (-inf: no sink), both
        # measured against the larger of the running maximum and the sink
        top = jnp.maximum(m, sink)
        shrink = jnp.exp(m - top)
        den = l * shrink + jnp.exp(sink - top)
        den = jnp.where(den == 0.0, 1.0, den)           # nothing seen: zeros
        o_ref[0] = (acc[:] * (shrink / den)[:, None]).astype(o_ref.dtype)


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
                    k_pos: jax.Array, *, scale: float, window: Optional[int] = None,
                    sink: Optional[jax.Array] = None, block_q: int = 512,
                    block_k: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    """q ``[kv_heads, rows, qk]``, k ``[kv_heads, keys, qk]``, v ``[kv_heads,
    keys, v]``; ``q_pos`` [rows] and ``k_pos`` [keys] int32 (``NOWHERE``: the
    key holds nothing); ``sink`` [kv_heads, rows] float32 or None. Returns ``[kv_heads,
    rows, v]`` in q's type: softmax over the keys with ``0 <= q_pos - k_pos <
    window`` (None: every earlier key) of ``q . k * scale``, times v.

    ``rows`` must be a multiple of ``min(block_q, rows)``; the keys are
    padded here to a multiple of the key block."""
    heads, rows, _ = q.shape
    keys, dv = k.shape[1], v.shape[2]
    bq = min(block_q, rows)
    if rows % bq:
        raise ValueError(f"{rows} query rows are not a multiple of {bq}")
    bk = min(block_k, -(-keys // _LANE) * _LANE)
    pad = -keys % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=NOWHERE)
    nq, nk = rows // bq, (keys + pad) // bk
    window = NOWHERE if window is None else int(window)
    q_pos, k_pos = q_pos.astype(jnp.int32), k_pos.astype(jnp.int32)
    held = k_pos < NOWHERE
    bounds = (q_pos.reshape(nq, bq).min(1), q_pos.reshape(nq, bq).max(1),
              jnp.where(held, k_pos, NOWHERE).reshape(nk, bk).min(1),
              jnp.where(held, k_pos, -NOWHERE).reshape(nk, bk).max(1))
    if sink is None:
        sink = jnp.full((heads, rows), -jnp.inf, jnp.float32)
    if interpret is None:
        interpret = _interpret_default()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(heads, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, q.shape[2]), lambda h, i, j, *_: (h, i, 0)),
            pl.BlockSpec((1, bk, k.shape[2]), lambda h, i, j, *_: (h, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda h, i, j, *_: (h, j, 0)),
            pl.BlockSpec((bq, 1), lambda h, i, j, *_: (i, 0)),
            pl.BlockSpec((1, bk), lambda h, i, j, *_: (0, j)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j, *_: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda h, i, j, *_: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                        pltpu.VMEM((bq, _LANE), jnp.float32),
                        pltpu.VMEM((bq, _LANE), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), window=window, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="chunk_attention",
    )(*bounds, q, k, v, q_pos[:, None], k_pos[None, :],
      sink.astype(jnp.float32)[:, :, None])
