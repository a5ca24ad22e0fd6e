"""Plain float32 ``jax.numpy`` reference of ``parallel/composite.py``'s
train step on ONE device: no mesh, no gathers, no psums.

Imports nothing of the program; reads the benchmark's canonical arrays
(``benchmark/weights.py``). ``composite``'s block is model code of its own
and is NOT the published GPT-2 block (stated under ``assumed`` in the
configuration): LayerNorm with a scale and no bias (eps 1e-5), no position
information at all, tanh-GELU, tied head, plain SGD, and a loss over ALL
positions in which the last one predicts the row's first token
(``jnp.roll``). The program runs its float32 matmuls at the TPU's default
precision (one bfloat16 pass); the reference runs them at ``highest``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .gpt import Cast, _mm, gelu_tanh

LN_EPS = 1e-5


def ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale


def block(h, p: Dict[str, jax.Array], heads: int, cast: Cast):
    x = ln(h, p["ln1_scale"])
    qkv = _mm("bsd,drh->bsrh", x, p["wqkv"], cast)           # [b, s, 3, d]
    b, s, _, d = qkv.shape
    hd = d // heads
    q, k, v = (qkv[:, :, i].reshape(b, s, heads, hd) for i in range(3))
    scores = _mm("bqhk,bshk->bhqs", q, k, cast) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    attn = _mm("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v, cast)
    h = h + _mm("bsd,de->bse", attn.reshape(b, s, d), p["wo"], cast)
    x = ln(h, p["ln2_scale"])
    return h + _mm("bsf,fd->bsd", gelu_tanh(_mm("bsd,df->bsf", x, p["w1"], cast)),
                   p["w2"], cast)


_PER_LAYER = ("ln1_scale", "ln2_scale", "wqkv", "wo", "w1", "w2")


def loss_sum(canon, ids: jax.Array, heads: int, cast: Cast = None):
    h = canon["embed"][ids]
    layers = {k: canon[k] for k in _PER_LAYER}

    def body(h, p):
        return jax.checkpoint(lambda hh, pp: block(hh, pp, heads, cast))(h, p), None

    h, _ = jax.lax.scan(body, h, layers)
    lg = _mm("bsd,vd->bsv", h, canon["embed"], cast)
    tgt = jnp.roll(ids, -1, axis=-1)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0])


def loss_and_grad(canon, ids: jax.Array, heads: int, cast: Cast = None,
                  rows_per_block: int = 0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``ids`` [rows, seq]. All rows in one block by default (the 774M
    model's gradient is 3 GB: a second copy to accumulate into would not
    fit beside the weights); ``rows_per_block`` > 0 accumulates instead."""
    count = ids.size
    if rows_per_block <= 0 or rows_per_block >= ids.shape[0]:
        loss, grad = jax.value_and_grad(loss_sum)(canon, ids, heads, cast)
    else:
        blocks = ids.reshape(-1, rows_per_block, ids.shape[-1])

        def body(acc, rows):
            loss, grad = jax.value_and_grad(loss_sum)(canon, rows, heads, cast)
            return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grad)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, canon))
        (loss, grad), _ = jax.lax.scan(body, zero, blocks)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grad)


def sgd_step(canon, grad, lr: float):
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, canon, grad)
