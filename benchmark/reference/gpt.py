"""Plain float32 ``jax.numpy`` reference of what ``GptLM`` computes.

Imports nothing of the program. Reads the benchmark's canonical arrays
(``benchmark/weights.py``). Every matmul runs at
``jax.default_matmul_precision("highest")`` unless a lower-precision
``cast`` is given (the control: operands rounded before every matmul).

What it implements is the repo's ``GptLM``, which departs from the
published GPT-2 block in two ways (stated in the configuration files under
``assumed``): rotary positions (half-split, theta 10000) in place of the
learned position table, and no bias terms on the projections. Otherwise as
published: pre-LayerNorm (eps 1e-6, scale and bias), tanh-GELU, tied head,
next-token cross entropy over positions 0..L-2.

``GptLM`` itself keeps activations and the residual stream in bfloat16;
the reference is float32 throughout — the gap between them is what the
limits in ``benchmark/cells/*.json`` were read from.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Cast = Optional[Callable[[jax.Array], jax.Array]]

LN_EPS = 1e-6
ROPE_THETA = 10000.0


def _round_to(x: jax.Array, dtype, top: float) -> jax.Array:
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_cast(x: jax.Array) -> jax.Array:
    """The control's rounding, the way fp8 matmuls are fed: operands in
    float8 e4m3 with one scale per tensor (amax to 448) going forward,
    cotangents in float8 e5m2 with one scale per tensor (amax to 57344)
    going back — the nearest precision below the bfloat16 that the
    configurations state."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


fp8_cast.defvjp(lambda x: (fp8_cast(x), None),
                lambda _, g: (_round_to(g, jnp.float8_e5m2, 57344.0),))


def _mm(spec: str, a: jax.Array, b: jax.Array, cast: Cast) -> jax.Array:
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def rope(x: jax.Array) -> jax.Array:
    """x: [b, L, h, k]; positions 0..L-1; rotate (first half, second half)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (ROPE_THETA ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p: Dict[str, jax.Array], cast: Cast):
    """One transformer block; ``p`` holds ONE layer's canonical arrays."""
    h = layer_norm(x, p["ln_attn_scale"], p["ln_attn_bias"])
    q = rope(_mm("bld,dhk->blhk", h, p["wq"], cast))
    k = rope(_mm("bld,dhk->blhk", h, p["wk"], cast))
    v = _mm("bld,dhk->blhk", h, p["wv"], cast)
    scores = _mm("bqhk,bshk->bhqs", q, k, cast) * (q.shape[-1] ** -0.5)
    L = x.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((L, L), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bhqs,bshk->bqhk", probs, v, cast)
    x = x + _mm("blhk,hkd->bld", ctx, p["wo"], cast)
    h = layer_norm(x, p["ln_mlp_scale"], p["ln_mlp_bias"])
    up = gelu_tanh(_mm("bld,df->blf", h, p["w_up"], cast))
    return x + _mm("blf,fd->bld", up, p["w_down"], cast)


_PER_LAYER = ("wq", "wk", "wv", "wo", "w_up", "w_down", "ln_attn_scale",
              "ln_attn_bias", "ln_mlp_scale", "ln_mlp_bias")


def hidden(canon: Dict[str, jax.Array], ids: jax.Array, cast: Cast = None):
    """Final-norm hidden states [b, L, d], layer by layer (each layer
    rematerialized in backward so a full-width row block fits)."""
    x = canon["embedding"][ids]
    layers = {k: canon[k] for k in _PER_LAYER}

    def body(x, p):
        return jax.checkpoint(lambda xx, pp: block(xx, pp, cast))(x, p), None

    x, _ = jax.lax.scan(body, x, layers)
    return layer_norm(x, canon["ln_final_scale"], canon["ln_final_bias"])


def logits_at(canon, h: jax.Array, cast: Cast = None) -> jax.Array:
    return _mm("...d,vd->...v", h, canon["embedding"], cast)


def loss_sum(canon, ids: jax.Array, cast: Cast = None) -> jax.Array:
    """SUM of next-token cross entropies over a block of rows (the caller
    divides by the whole batch's count)."""
    h = hidden(canon, ids, cast)[:, :-1]
    lg = logits_at(canon, h, cast)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


def loss_and_grad(canon, ids: jax.Array, rows_per_block: int = 1,
                  cast: Cast = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean loss over the batch and its gradient, accumulated over blocks
    of rows so that activations of one block at a time are live."""
    b, L = ids.shape
    blocks = ids.reshape(b // rows_per_block, rows_per_block, L)
    count = b * (L - 1)

    def body(acc, rows):
        loss, grad = jax.value_and_grad(loss_sum)(canon, rows, cast)
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grad)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, canon))
    (loss, grad), _ = jax.lax.scan(body, zero, blocks)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grad)


def adamw_step(canon, state: Dict[str, Any], grad, *, lr: float, b1: float,
               b2: float, eps: float, weight_decay: float):
    """Plain AdamW as ``optax.adamw`` defines it: bias-corrected moments,
    decoupled decay added to the update, all scaled by ``lr``."""
    t = state["t"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grad)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"], grad)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, n):
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p)

    new = jax.tree_util.tree_map(upd, canon, mu, nu)
    return new, {"t": t, "mu": mu, "nu": nu}


def adamw_init(canon) -> Dict[str, Any]:
    z = jax.tree_util.tree_map(jnp.zeros_like, canon)
    return {"t": jnp.zeros((), jnp.float32), "mu": z,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, canon)}


def gaps_under_best(ref_logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """How far each token's reference logit lies under the reference's best
    at its position, in standard deviations of that position's logits."""
    top = ref_logits.max(-1)
    pick = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return (top - pick) / ref_logits.std(-1)
