"""The head and the greedy choice in one walk over the vocabulary: the
serving path's kernel for a decoder that needs of each row's logits only
the best id and its share of the softmax (models/sdar.py: a block-diffusion
pass over ``slots x block_len`` rows and 151,936 ids at temperature 0).

``x`` [rows, d] times ``head`` [d, vocab] is ``rows x vocab`` float32
logits, 155 MB at the cell's shape, of which the caller keeps three numbers
a row: the largest logit ``m``, the FIRST id that holds it, and the
log-sum-exp. All three can be carried across vocabulary tiles (a running
maximum with its id, and ``s = sum(exp(logit - m))`` rescaled by ``exp(m_old
- m_new)`` where the maximum moves: the blockwise loss of the training path
carries its log-sum-exp the same way, ``models/gpt.blockwise_causal_lm_loss``),
so the logits never leave the chip's fast memory. The grid walks the
vocabulary: ``x`` stays resident, a tile of the head is fetched once (the
next in flight behind this one's product), the tile's logits are a float32
product in VMEM and are reduced there. A vocabulary that is no whole number
of tiles (151,936 = 128 x 1,187, and 1,187 is prime) is masked by column id
in the last tile, not padded in HBM. Operands as they are, float32
accumulation, float32 ``m``, ``s`` and log-sum-exp. A tie goes to the lower
id, as ``jnp.argmax`` gives it: inside a tile the least id among the
largest, between tiles the earlier.

One tiling. A shape whose resident blocks do not fit the fast memory
(thousands of rows, or a contraction so long that one 128-column tile of the
head does not fit) takes :func:`materialised`, the same three numbers from
logits written out, and ticks ``ops_fused_fallback_total{kernel=
"head_choice"}``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fallback import record_fallback
from .flash_attention import _interpret_default

#: columns of the head a grid step: ``[2048, 1024]`` bfloat16 is 4 MB, two in
#: flight; PERF.md section 6, PR 38 has the tiles measured
_VOCAB_TILE = 1024
#: what the resident blocks and the tile's float32 temporaries may take
_VMEM_BUDGET = 48 << 20


def _kernel(x_ref, w_ref, ids_ref, max_ref, lse_ref, *, vocab: int, tn: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        ids_ref[...] = jnp.zeros_like(ids_ref)
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)
        lse_ref[...] = jnp.zeros_like(lse_ref)

    logits = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    col = j * tn + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # past the vocabulary the tile holds whatever lay behind the array
    logits = jnp.where(col < vocab, logits, -jnp.inf)
    best = jnp.max(logits, axis=-1, keepdims=True)
    first = jnp.min(jnp.where(logits == best, col, jnp.iinfo(jnp.int32).max),
                    axis=-1, keepdims=True)
    m_old = max_ref[...]
    m_new = jnp.maximum(m_old, best)
    ids_ref[...] = jnp.where(best > m_old, first, ids_ref[...])
    max_ref[...] = m_new
    # until the last tile the third result holds the sum, then its logarithm
    s = lse_ref[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True)
    last = j == pl.num_programs(0) - 1
    lse_ref[...] = jnp.where(last, m_new + jnp.log(s), s)


def _blocks_bytes(rows: int, d: int, tn: int, itemsize: int) -> int:
    """``x`` and a tile of the head, two buffers each, and the tile's
    logits with the temporaries of their reductions, float32."""
    return 2 * rows * d * itemsize + 2 * d * tn * itemsize + 6 * rows * tn * 4


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def _call(x: jax.Array, head: jax.Array, tn: int, interpret: bool):
    rows, d = x.shape
    vocab = head.shape[1]
    resident = lambda shape: pl.BlockSpec(shape, lambda j: (0, 0))
    ids, m, lse = pl.pallas_call(
        functools.partial(_kernel, vocab=vocab, tn=tn),
        grid=(pl.cdiv(vocab, tn),),
        in_specs=[resident((rows, d)), pl.BlockSpec((d, tn), lambda j: (0, j))],
        out_specs=[resident((rows, 1))] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_blocks_bytes(rows, d, tn, x.dtype.itemsize) + (8 << 20)),
        interpret=interpret,
        name="head_choice",
    )(x, head)
    return ids[:, 0], m[:, 0], lse[:, 0]


def materialised(x: jax.Array, head: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What :func:`head_choice` returns, from ``rows x vocab`` float32
    logits written out and reduced row by row."""
    logits = jnp.dot(x, head, preferred_element_type=jnp.float32)
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32), jnp.max(logits, axis=-1),
            jax.nn.logsumexp(logits, axis=-1))


def head_choice(x: jax.Array, head: jax.Array, *, interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``x`` [rows, d], ``head`` [d, vocab], one type. Returns, a row of
    ``x @ head`` in float32: the first id of its largest logit (int32
    [rows]), that logit and the row's log-sum-exp (float32 [rows]); the
    id's share of the softmax is ``exp(logit - log-sum-exp)``."""
    (rows, d), vocab = x.shape, head.shape[1]
    if head.shape[0] != d or x.dtype != head.dtype:
        raise ValueError(f"rows {x.shape} {x.dtype} do not match a head {head.shape} {head.dtype}")
    tn = min(vocab, _VOCAB_TILE)
    if _blocks_bytes(rows, d, tn, x.dtype.itemsize) > _VMEM_BUDGET:
        record_fallback("head_choice", f"{rows} rows of {d} beside a head tile of {tn} columns "
                                       "do not fit the fast memory")
        return materialised(x, head)
    return _call(x, head, tn, _interpret_default() if interpret is None else interpret)
