"""Plain float32 ``jax.numpy`` reference of the EvaByte language model's
forward pass (EVA attention in the chunked form of the EvaByte release), at
the cut the configuration states. Imports nothing of the program. No
cache, no batching, no kernels: one sequence, one layer at a time (the
caller makes each layer's weights from the seed, ``weights_eva``, and drops
them before the next); the MLP in blocks of rows and the attention a window
of queries at a time, so that a padded sequence of 32,768 positions (537 MB
a ``[L, 4096]`` float32 array) fits beside a layer's weights. Every matmul
runs at ``highest`` precision unless a lower-precision ``cast`` is given
(the control: operands rounded before every matmul).

Equations (x: the residual stream, float32; per token t; H heads of d; window
W, chunk C, s = d ** -0.5; ``s`` below is the sizes dict):
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)            (unit offset)
  q_t, k_t, v_t = Wq h, Wk h, Wv h, h = RMSNorm(x_t); rotary (half-split,
  theta, every one of the d dims) on q_t, k_t at position t.
  window of t: w(t) = floor(t / W); chunk c covers positions [C c, C c + C).
  Summary of chunk c, head i (mu_i, phi_i in R^d), over the ROTATED keys:
    k~_c = sum_{j in c} softmax_j(s k_j . mu_i) k_j
    v~_c = sum_{j in c} softmax_j(s k_j . phi_i) v_j
  local set  L(t) = {j : w(j) = w(t), j <= t}          (exact keys)
  remote set R(t) = {c : C c + C - 1 < W w(t)}         (every chunk of every EARLIER window)
  ONE softmax over both:
    Z = sum_{j in L} exp(s q_t . k_j) + sum_{c in R} exp(s q_t . k~_c)
    o_t = (sum_{j in L} exp(s q_t . k_j) v_j + sum_{c in R} exp(s q_t . k~_c) v~_c) / Z
  attention out = Wo concat_i(o_t)
  x <- x + Attn(RMSNorm(x)); x <- x + Wdown(silu(Wgate h') * Wup h'), h' = RMSNorm(x)
  final RMSNorm; logits = head 0 of the untied output head, float32.

Departures from the release known to the builder (none mended silently;
``benchmark/configs/evabyte.json`` lists them under ``assumed``): EVA as
published (arXiv:2302.04542) estimates the remote terms of the softmax with
control variates over random features; this is the chunked form with one
LEARNED summary a chunk and head, and the pooling form above (a softmax of
``s k . mu`` over the chunk's keys, of ``s k . phi`` for its values) is
ISSUE 32's reading of the release's ``adaptive_mu_k`` / ``adaptive_phi``
parameters, which ``config.json`` does not settle: the release's modelling
code may pool otherwise. Summaries get no rotary of their own. Heads 1-7 of
``num_pred_heads`` (multibyte prediction) are not held.

``fault`` computes a WRONG model on purpose, for the readings the cell's
limit is set from: ``no_remote`` (the summaries left out), ``mean_pool``
(mu and phi ignored: a chunk's plain mean), ``stale_rollover`` (the newest
completed window's summaries not visible: the off-by-one a wrong roll-over
makes).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

Cast = Optional[Callable[[jax.Array], jax.Array]]
ROW_BLOCK = 2048


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, theta: float):
    """x [L, heads, dim] at positions 0..L-1; rotates (first half, second
    half) of every dim."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(s: Dict[str, Any], w, k, v, cast: Cast, fault: Optional[str]):
    """k (rotated), v [L, H, d] -> k~, v~ [L / C, H, d]."""
    C = s["chunk_size"]
    kc = k.reshape(-1, C, *k.shape[1:])
    vc = v.reshape(-1, C, *v.shape[1:])
    scale = k.shape[-1] ** -0.5
    if fault == "mean_pool":
        return kc.mean(1), vc.mean(1)
    w_k = jax.nn.softmax(scale * _mm("nchd,hd->nch", kc, w["mu"], cast), axis=1)
    w_v = jax.nn.softmax(scale * _mm("nchd,hd->nch", kc, w["phi"], cast), axis=1)
    return _mm("nch,nchd->nhd", w_k, kc, cast), _mm("nch,nchd->nhd", w_v, vc, cast)


def attention(s: Dict[str, Any], w, h, cast: Cast, fault: Optional[str]):
    """h [L, d_model], L a multiple of the window."""
    L, H, d, W = h.shape[0], s["n_heads"], s["head_dim"], s["window"]
    split = lambda y: y.reshape(L, H, d)
    q = rope(split(_mm("ld,dm->lm", h, w["wq"], cast)), s["rope_theta"])
    k = rope(split(_mm("ld,dm->lm", h, w["wk"], cast)), s["rope_theta"])
    v = split(_mm("ld,dm->lm", h, w["wv"], cast))
    ks, vs = summaries(s, w, k, v, cast, fault)
    chunk = jnp.arange(ks.shape[0])
    per_window = W // s["chunk_size"]
    scale = d ** -0.5
    causal = jnp.arange(W)[:, None] >= jnp.arange(W)[None, :]

    def one_window(args):
        qw, kw, vw, n = args                        # [W, H, d] each; the window's number
        local = jnp.where(causal[None], _mm("qhd,jhd->hqj", qw, kw, cast) * scale, -jnp.inf)
        seen = n - 1 if fault == "stale_rollover" else n
        visible = (chunk < seen * per_window) & (fault != "no_remote")
        remote = jnp.where(visible[None, None, :],
                           _mm("qhd,chd->hqc", qw, ks, cast) * scale, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([local, remote], axis=-1), axis=-1)
        return _mm("hqj,jhd->qhd", p[..., :W], vw, cast) \
            + _mm("hqc,chd->qhd", p[..., W:], vs, cast)

    by_window = lambda y: y.reshape(L // W, W, H, d)
    out = jax.lax.map(one_window, (by_window(q), by_window(k), by_window(v),
                                   jnp.arange(L // W)))
    return _mm("lm,md->ld", out.reshape(L, H * d), w["wo"], cast)


def swiglu(h, w_gate, w_up, w_down, cast: Cast):
    def rows(hb):
        return _mm("lf,fd->ld", jax.nn.silu(_mm("ld,df->lf", hb, w_gate, cast))
                   * _mm("ld,df->lf", hb, w_up, cast), w_down, cast)

    block = min(h.shape[0], ROW_BLOCK)
    return jax.lax.map(rows, h.reshape(-1, block, h.shape[1])).reshape(h.shape)


def block(s: Dict[str, Any], w, x, *, cast: Cast = None, fault: Optional[str] = None):
    """One layer over one sequence x [L, d_model] (L a multiple of the
    window and of ``ROW_BLOCK`` or under it); ``w``: that layer's
    canonical arrays."""
    x = x + attention(s, w, rms_norm(x, w["norm_attn"], s["norm_eps"]), cast, fault)
    return x + swiglu(rms_norm(x, w["norm_ffn"], s["norm_eps"]),
                      w["w_gate"], w["w_up"], w["w_down"], cast)


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
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("fs", "cast", "fault"))
def block_jit(fs, w, x, *, cast=None, fault=None):
    return block(dict(fs), w, x, cast=cast, fault=fault)
