"""Plain float32 ``jax.numpy`` reference of the MiMo-V2 language model's
forward pass, at the cut the configuration states. Imports nothing of the
program. No cache, no batching: one sequence, one layer at a time (the
caller makes each layer's weights from the seed, ``weights_mimo``, and
drops them before the next), attention scores in blocks of query rows.
Every matmul runs at ``highest`` precision unless a lower-precision
``cast`` is given (the control: operands rounded before every matmul).

Equations (x: the residual stream, per token t):
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
  Attention of kind full / window: q = Wq h [H, qk], k = Wk h [K, qk],
  v = Wv h [K, v]; rotary (half-split) with the kind's theta on dims
  0..rotary-1 of q and k; query head i reads KV head i // (H / K);
  l_tj = q_t . k_j / sqrt(qk) for j <= t, in window layers only for
  t - j < window; full: softmax; window, with the sink s_i of head i:
  p_tj = exp(l_tj) / (exp(s_i) + sum_j' exp(l_tj')); o_t = value_scale *
  sum_j p_tj v_j; output Wo concat_i(o).
  Dense MLP: Wdown(silu(Wgate h) * Wup h).
  Expert layer: s = sigmoid(Wr h) over ALL experts; S = top_k(s + b);
  w_e = s_e / sum_{e' in S} s_e'; result sum_{e in S, held} w_e E_e(h):
  what the experts held elsewhere would add is left out.
  x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x)); final RMSNorm;
  logits over the held slice of the vocabulary.

``fault`` computes a WRONG model on purpose, for the readings the cell's
limit is set from: ``no_window`` (window layers attend to every earlier
position), ``no_sink`` (the sink left out of the denominator), ``top7``
(one expert fewer chosen).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

Cast = Optional[Callable[[jax.Array], jax.Array]]
QUERY_BLOCK = 512


def _round_to(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fp8_cast(x: jax.Array) -> jax.Array:
    """The control's rounding: float8 e4m3, one scale a tensor."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def _mm(spec: str, a, b, cast: Cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float, rotary: int):
    """x [L, heads, dim] at positions 0..L-1; rotates (first half, second
    half) of the first ``rotary`` dims."""
    half = rotary // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def attention(s: Dict[str, Any], w, h, window: bool, cast: Cast, fault: Optional[str]):
    L = h.shape[0]
    theta = s["rope_theta_window"] if window else s["rope_theta_full"]
    q = rope(_mm("ld,dhk->lhk", h, w["wq"], cast), theta, s["rotary_dim"])
    k = rope(_mm("ld,dhk->lhk", h, w["wk"], cast), theta, s["rotary_dim"])
    v = _mm("ld,dhk->lhk", h, w["wv"], cast)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)   # head i <- i // group
    block = min(L, QUERY_BLOCK)
    j = jnp.arange(L)
    banded = window and fault != "no_window"
    sunk = window and fault != "no_sink"

    def rows(args):
        qb, t = args                                              # [block, H, qk], [block]
        logits = _mm("qhk,jhk->hqj", qb, k, cast) * (q.shape[-1] ** -0.5)
        gap = t[:, None] - j[None, :]
        mask = (gap >= 0) & ((gap < s["window"]) if banded else True)
        logits = jnp.where(mask[None], logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        if sunk:
            top = jnp.maximum(top, w["sink"][:, None, None])
        e = jnp.exp(logits - top)
        den = e.sum(-1, keepdims=True)
        if sunk:
            den = den + jnp.exp(w["sink"][:, None, None] - top)
        return _mm("hqj,jhd->qhd", e / den, v, cast)

    out = jax.lax.map(rows, (q.reshape(L // block, block, *q.shape[1:]),
                             j.reshape(L // block, block)))
    out = s["value_scale"] * out.reshape(L, *out.shape[2:])
    return _mm("lhd,hdm->lm", out, w["wo"], cast)


def swiglu(h, w_gate, w_up, w_down, cast: Cast):
    return _mm("lf,fd->ld", jax.nn.silu(_mm("ld,df->lf", h, w_gate, cast))
               * _mm("ld,df->lf", h, w_up, cast), w_down, cast)


def expert_layer(s: Dict[str, Any], w, h, cast: Cast, fault: Optional[str],
                 first_held: int = 0):
    k = s["experts_per_token"] - (1 if fault == "top7" else 0)
    scores = jax.nn.sigmoid(_mm("ld,de->le", h, w["router"], None))   # float32, never cast
    _, chosen = jax.lax.top_k(scores + w["router_bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / picked.sum(-1, keepdims=True)                   # over all k chosen

    def one(acc, args):
        e, wg, wu, wd = args
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)  # [L]; 0: not chosen
        return acc + mine[:, None] * swiglu(h, wg, wu, wd, cast), None

    held = w["w_gate"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (first_held + jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return acc


def block(s: Dict[str, Any], w, x, *, window: bool, moe: bool, cast: Cast = None,
          fault: Optional[str] = None, first_held: int = 0):
    """One layer over one sequence x [L, d]; ``w``: that layer's canonical
    arrays."""
    x = x + attention(s, w, rms_norm(x, w["norm_attn"], s["norm_eps"]), window, cast, fault)
    h = rms_norm(x, w["norm_ffn"], s["norm_eps"])
    if moe:
        return x + expert_layer(s, w, h, cast, fault, first_held)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], cast)


def logits_at(s: Dict[str, Any], top, h, cast: Cast = None):
    return _mm("...d,dv->...v", rms_norm(h, top["norm_final"], s["norm_eps"]),
               top["head"], cast)


def gaps_under_best(ref_logits, tokens):
    """How far each token's reference logit lies under the reference's best
    at its position, in standard deviations of that position's logits."""
    best = ref_logits.max(-1)
    pick = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return (best - pick) / ref_logits.std(-1)


def frozen(s: Dict[str, Any]):
    """``s`` as something ``jax.jit`` can take as a static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in s.items()))


@functools.partial(jax.jit, static_argnames=("fs", "window", "moe", "cast", "fault"))
def block_jit(fs, w, x, *, window, moe, cast=None, fault=None):
    return block(dict(fs), w, x, window=window, moe=moe, cast=cast, fault=fault)
