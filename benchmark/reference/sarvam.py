"""Plain float32 ``jax.numpy`` reference of the Sarvam language model's
forward pass (latent attention, DeepSeek-V2/V3 style without a query low
rank), at the cut the configuration states. Imports nothing of the program.
No cache, no batching, no kernels and NO absorption: the textbook
(expanded) form over one whole sequence, one layer at a time (the caller
makes each layer's weights from the seed, ``weights_sarvam``, and drops
them before the next), queries in blocks of 64 positions and the
feed-forward parts in blocks of 2,048 (what a 32,768-position sequence
needs at once fits beside a layer's float32 weights on one chip). Every
matmul runs at ``highest`` precision unless a lower-precision ``cast`` is
given (operands rounded before every matmul: to float8 for the control, to
bfloat16 for the configuration's own precision).

Equations (x: the residual stream, per token t, h = RMSNorm(x)):
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
  q = RMSNorm_192(h Wq) per head (use_qk_norm, read as a norm over each
  query head's nope + rope dims, one gain); q = [q_nope | RoPE(q_rope)].
  [c | k_r] = h Wdkv; c = RMSNorm_512(c); k_r = RoPE(k_r), one for all heads.
  Head i: k_i = [c Wuk_i | k_r], v_i = c Wuv_i; l_tj = q_t,i . k_j,i * scale
  for j <= t, scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor)
  + 1)^2; softmax; output Wo concat_i(o_i).
  RoPE (deepseek_yarn, half-split): inverse frequencies
  inv_i = f_i / factor * r_i + f_i * (1 - r_i), f_i = theta^(-2i / dim),
  r_i = clip((i - lo) / (hi - lo), 0, 1), lo = floor(b(beta_fast)), hi =
  ceil(b(beta_slow)), b(n) = dim ln(orig_max / (2 pi n)) / (2 ln theta); cos
  and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim).
  Dense MLP: Wdown(silu(Wgate h) * Wup h).
  Expert layer: s = sigmoid(Wr h) over ALL experts; S = top_k(s + b);
  w_e = s_e / sum_{e' in S} s_e'; result shared(h) + routed_scaling *
  sum_{e in S, held} w_e E_e(h): what the experts held elsewhere would add
  is left out.
  x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x)); final RMSNorm;
  logits over the held slice of the vocabulary.

Departures from the release's description: rotary is half-split where
DeepSeek's code interleaves pairs (under random weights a permutation of
the rotary columns); the weights are random from the seed; only the held
experts and the held vocabulary slice exist.

``fault`` computes a WRONG model on purpose, for the readings the cell's
limit is set from: ``no_mscale`` (the softmax scale without YaRN's
``mscale^2``), ``no_yarn`` (plain rotary, theta 1e4, no ramp),
``no_latent_norm`` (the latent's RMSNorm left out), ``no_routed_scaling``
(the routed part not scaled by 2.5), ``no_shared`` (the shared expert left
out).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

Cast = Optional[Callable[[jax.Array], jax.Array]]
QUERY_BLOCK = 64
ROW_BLOCK = 2048


def _round_to(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fp8_cast(x: jax.Array) -> jax.Array:
    """The control's rounding: float8 e4m3, one scale a tensor."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def bf16_cast(x: jax.Array) -> jax.Array:
    """The configuration's own precision: operands rounded to bfloat16 (a
    sound reading beside the program's, not a fault)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(spec: str, a, b, cast: Cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def correction_bounds(s: Dict[str, Any]):
    """YaRN's (lo, hi): the pair indices between which the ramp runs."""
    dim, base = s["qk_rope_dim"], s["rope_theta"]

    def b(n):
        return dim * math.log(s["rope_original_max"] / (2 * math.pi * n)) / (2 * math.log(base))

    return max(math.floor(b(s["beta_fast"])), 0), min(math.ceil(b(s["beta_slow"])), dim - 1)


def softmax_scale(s: Dict[str, Any], fault: Optional[str] = None) -> float:
    m = 1.0 if fault == "no_mscale" else mscale(s["rope_factor"], s["mscale_all_dim"])
    return (s["qk_nope_dim"] + s["qk_rope_dim"]) ** -0.5 * m * m


def inv_freq(s: Dict[str, Any], fault: Optional[str] = None):
    dim = s["qk_rope_dim"]
    f = 1.0 / (s["rope_theta"] ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if fault == "no_yarn":
        return f
    lo, hi = correction_bounds(s)
    r = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0, 1)
    return f / s["rope_factor"] * r + f * (1 - r)


def knobs(s: Dict[str, Any], fault: Optional[str] = None) -> Dict[str, Any]:
    """What a fault changes, as arrays: one compiled layer serves the sound
    model and every fault (a variant's own compile of a 32,768-position
    layer costs 10-14 s on the chip)."""
    f32 = jnp.float32
    return {"scale": jnp.asarray(softmax_scale(s, fault), f32),
            "inv_freq": inv_freq(s, fault),
            "latent_norm": jnp.asarray(fault != "no_latent_norm", f32),
            "routed_scale": jnp.asarray(1.0 if fault == "no_routed_scaling"
                                        else s["routed_scaling"], f32),
            "shared": jnp.asarray(fault != "no_shared", f32)}


def rope(s: Dict[str, Any], x, positions, inv):
    """x [L, (heads,) dim] at ``positions`` [L], half-split, with the
    inverse frequencies ``inv``."""
    half = s["qk_rope_dim"] // 2
    pos = positions.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1))
    ang = pos * inv
    m = mscale(s["rope_factor"], s["mscale"]) / mscale(s["rope_factor"], s["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(s: Dict[str, Any], w, h, cast: Cast, fault: Optional[str], k=None):
    k = knobs(s, fault) if k is None else k
    L, n, r = h.shape[0], s["qk_nope_dim"], s["kv_lora_rank"]
    ckr = _mm("ld,dc->lc", h, w["w_dkv"], cast)
    c = jnp.where(k["latent_norm"] > 0, rms_norm(ckr[:, :r], w["kv_norm"], s["norm_eps"]),
                  ckr[:, :r])
    k_rope = rope(s, ckr[:, r:], jnp.arange(L), k["inv_freq"])       # [L, rope]
    k_nope = _mm("lc,hcn->lhn", c, w["w_uk"], cast)                   # [L, H, nope]
    v = _mm("lc,hcv->lhv", c, w["w_uv"], cast)                        # [L, H, v]
    scale = k["scale"]
    block = min(L, QUERY_BLOCK)
    j = jnp.arange(L)

    def rows(args):
        hb, t = args                                                  # [block, d], [block]
        q = rms_norm(_mm("ld,dhk->lhk", hb, w["wq"], cast), w["q_norm"], s["norm_eps"])
        qn, qr = q[..., :n], rope(s, q[..., n:], t, k["inv_freq"])
        logits = (_mm("qhn,jhn->hqj", qn, k_nope, cast)
                  + _mm("qhr,jr->hqj", qr, k_rope, cast)) * scale
        logits = jnp.where((t[:, None] >= j[None, :])[None], logits, -jnp.inf)
        e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        o = _mm("hqj,jhv->qhv", e / e.sum(-1, keepdims=True), v, cast)
        return _mm("qhv,hvm->qm", o, w["wo"], cast)

    out = jax.lax.map(rows, (h.reshape(L // block, block, -1), j.reshape(L // block, block)))
    return out.reshape(L, -1)


def _by_rows(f, h):
    """``f`` over blocks of ``ROW_BLOCK`` rows of h [L, d]: the
    intermediates of a SwiGLU of 16,384 over 32,768 positions (6 GB in
    float32) never exist whole."""
    block = min(h.shape[0], ROW_BLOCK)
    out = jax.lax.map(f, h.reshape(h.shape[0] // block, block, -1))
    return out.reshape(h.shape[0], -1)


def swiglu(h, w_gate, w_up, w_down, cast: Cast):
    return _mm("lf,fd->ld", jax.nn.silu(_mm("ld,df->lf", h, w_gate, cast))
               * _mm("ld,df->lf", h, w_up, cast), w_down, cast)


def routed(s: Dict[str, Any], w, h, cast: Cast, first_held: int = 0):
    """The held experts' part, unscaled: sum over the chosen experts that
    are held of weight x expert(h)."""
    held = w["w_gate"].shape[0]

    def rows(hb):
        scores = jax.nn.sigmoid(_mm("ld,de->le", hb, w["router"], None))  # float32, never cast
        _, chosen = jax.lax.top_k(scores + w["router_bias"], s["experts_per_token"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / picked.sum(-1, keepdims=True)                  # over all k chosen

        def one(acc, args):
            e, wg, wu, wd = args
            mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)  # 0: not chosen
            return acc + mine[:, None] * swiglu(hb, wg, wu, wd, cast), None

        acc, _ = jax.lax.scan(one, jnp.zeros_like(hb), (first_held + jnp.arange(held),
                                                        w["w_gate"], w["w_up"], w["w_down"]))
        return acc

    return _by_rows(rows, h)


def expert_layer(s: Dict[str, Any], w, h, cast: Cast, fault: Optional[str],
                 first_held: int = 0, k=None):
    k = knobs(s, fault) if k is None else k
    shared = _by_rows(lambda hb: swiglu(hb, w["shared_gate"], w["shared_up"],
                                        w["shared_down"], cast), h)
    return k["routed_scale"] * routed(s, w, h, cast, first_held) + k["shared"] * shared


def block(s: Dict[str, Any], w, x, *, moe: bool, cast: Cast = None,
          fault: Optional[str] = None, first_held: int = 0, k=None):
    """One layer over one sequence x [L, d]; ``w``: that layer's canonical
    arrays; ``k``: the knobs of ``fault`` (made here where not given)."""
    k = knobs(s, fault) if k is None else k
    x = x + attention(s, w, rms_norm(x, w["norm_attn"], s["norm_eps"]), cast, None, k)
    h = rms_norm(x, w["norm_ffn"], s["norm_eps"])
    if moe:
        return x + expert_layer(s, w, h, cast, None, first_held, k)
    return x + _by_rows(lambda hb: swiglu(hb, w["w_gate"], w["w_up"], w["w_down"], cast), h)


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


@functools.partial(jax.jit, static_argnames=("fs", "moe", "cast"))
def block_jit(fs, w, x, k, *, moe, cast=None):
    """``block`` with the fault's knobs ``k`` (:func:`knobs`) as arrays."""
    return block(dict(fs), w, x, moe=moe, cast=cast, k=k)
