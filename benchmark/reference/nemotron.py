"""Plain float32 ``jax.numpy`` reference of the Nemotron-H hybrid language
model's forward pass (Mamba-2 state-space layers, latent expert layers of
relu² experts, GQA attention) at the cut the configuration states. Imports
nothing of the program. No cache, no batching, no kernels and NO chunked
scan: the Mamba layer runs its recurrence ONE POSITION AT A TIME over the
whole sequence (``jax.lax.scan``), one layer and one sequence at a time
(the caller makes each layer's weights from the seed, ``weights_nemotron``,
and drops them before the next); attention takes queries in blocks of 64
positions and the expert layer rows in blocks of 2,048 (a sequence whose
length 2,048 does not divide in one block). Every matmul runs
at ``highest`` precision unless a lower-precision ``cast`` is given
(operands rounded before every matmul: to float8 for the control, to
bfloat16 for the configuration's own precision); the recurrence itself is
float32 elementwise work and is never cast.

Equations (x: the residual stream, per position t, h = RMSNorm(x)):
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g; every layer x <- x + mix(h).
  M (Mamba-2): [z | xBC | dt] = h W_in; xBC = silu(sum_j w_j xBC_{t-K+1+j} +
  b) (depthwise, width K, zeros before the sequence); xBC = [x | B | C], x
  [H, P], B and C [G, N], head h reads group h // (H / G); dt = softplus(dt
  + dt_bias), A = -exp(A_log); S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t;
  y = S_t C_t + D x_t; y = RMSNorm over each of G groups of channels of (y *
  silu(z)), one gain; out = y W_out.
  * (attention): q = h Wq (32 heads), k = h Wk, v = h Wv (2 KV heads, each
  shared by 16 query heads), no rotary; causal softmax at head_dim^-0.5;
  out = Wo concat(o).
  E (latent experts): s = sigmoid(h Wr) over ALL experts (float32, never
  cast); S = top_k(s + b); w_e = s_e / sum_{e' in S} s_e'; h_L = h W_down;
  out = relu(h Ws1)^2 Ws2 + routed_scaling * (sum_{e in S, held} w_e
  relu(h_L W1_e)^2 W2_e) W_up: what the experts held elsewhere would add is
  left out.
  Final RMSNorm; logits over the held slice of the vocabulary.

``fault`` computes a WRONG model on purpose, for the readings the cell's
limit is set from: ``state_reset`` (each Mamba state zeroed at every
prefill chunk's first position, as a program that did not carry it would),
``conv_not_carried`` (the convolution sees zeros before every prefill
chunk), ``no_D`` (the skip term left out), ``no_gate`` (``silu(z)`` left out
of the norm), ``no_routed_scaling`` (the routed part not scaled by 5). The
first two need the prompt's length and the chunk: :func:`knobs`.
"""

from __future__ import annotations

import functools
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


def knobs(s: Dict[str, Any], length: int, prompt: int = 0, chunk: int = 0,
          fault: Optional[str] = None) -> Dict[str, Any]:
    """What a fault changes, as arrays over a sequence of ``length``
    positions whose first ``prompt`` were prefilled in chunks of ``chunk``:
    one compiled layer serves the sound model and every fault.
    ``state_reset[t]``: the state is zeroed before position t;
    ``conv_from[t]``: the first position whose input the convolution at t
    may see."""
    f32 = jnp.float32
    t = jnp.arange(length)
    starts = (jnp.minimum(t, max(prompt - 1, 0)) // max(chunk, 1)) * chunk
    return {
        "state_reset": (t > 0) & (t < prompt) & (t % max(chunk, 1) == 0)
        if fault == "state_reset" else jnp.zeros((length,), bool),
        "conv_from": starts if fault == "conv_not_carried" else jnp.zeros((length,), t.dtype),
        "skip": jnp.asarray(fault != "no_D", f32),
        "gate": jnp.asarray(fault != "no_gate", f32),
        "routed_scale": jnp.asarray(1.0 if fault == "no_routed_scaling"
                                    else s["routed_scaling"], f32)}


def mamba(s: Dict[str, Any], w, h, cast: Cast, k):
    """One Mamba-2 mixer over a whole sequence h [L, d], the recurrence a
    position at a time."""
    L = h.shape[0]
    H, P, G, N, K = (s["mamba_heads"], s["mamba_head_dim"], s["n_groups"],
                     s["ssm_state"], s["conv_kernel"])
    di, gn = H * P, G * N
    zxbcdt = _mm("ld,de->le", h, w["w_in"], cast)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn], zxbcdt[:, 2 * di + 2 * gn:]
    t = jnp.arange(L)
    conv = w["conv_b"] + sum(
        w["conv_w"][j] * jnp.where(((t - (K - 1 - j)) >= k["conv_from"])[:, None],
                                   jnp.roll(xbc, K - 1 - j, axis=0), 0.0)
        for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(L, G, H // G, P)
    B, C = xbc[:, di:di + gn].reshape(L, G, N), xbc[:, di + gn:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(L, G, H // G)
    A = -jnp.exp(w["A_log"]).reshape(G, H // G)

    def step(S, pos):
        xt, dtt, bt, ct, reset = pos
        S = jnp.where(reset, 0.0, S)
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return S, jnp.einsum("gjpn,gn->gjp", S, ct, precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((G, H // G, P, N), jnp.float32),
                        (x, dt, B, C, k["state_reset"]))
    y = (y + k["skip"] * w["D"].reshape(G, H // G)[..., None] * x).reshape(L, di)
    y = y * jnp.where(k["gate"] > 0, jax.nn.silu(z), 1.0)
    y = rms_norm(y.reshape(L, G, di // G), 1.0, s["norm_eps"]).reshape(L, di) * w["mixer_norm"]
    return _mm("le,ed->ld", y, w["w_out"], cast)


def attention(s: Dict[str, Any], w, h, cast: Cast):
    L, kv, hd = h.shape[0], s["kv_heads"], s["head_dim"]
    g = s["n_heads"] // kv
    keys = _mm("ld,dkc->lkc", h, w["wk"], cast)
    vals = _mm("ld,dkc->lkc", h, w["wv"], cast)
    block = min(L, QUERY_BLOCK)
    j = jnp.arange(L)

    def rows(args):
        hb, t = args
        q = _mm("ld,dhc->lhc", hb, w["wq"], cast).reshape(-1, kv, g, hd)
        logits = _mm("qkgc,jkc->kgqj", q, keys, cast) * hd ** -0.5
        logits = jnp.where((t[:, None] >= j[None, :])[None, None], logits, -jnp.inf)
        e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        o = _mm("kgqj,jkc->qkgc", e / e.sum(-1, keepdims=True), vals, cast)
        return _mm("qhc,hcm->qm", o.reshape(-1, kv * g, hd), w["wo"], cast)

    out = jax.lax.map(rows, (h.reshape(L // block, block, -1), j.reshape(L // block, block)))
    return out.reshape(L, -1)


def _by_rows(f, h):
    block = ROW_BLOCK if h.shape[0] % ROW_BLOCK == 0 else h.shape[0]
    out = jax.lax.map(f, h.reshape(h.shape[0] // block, block, -1))
    return out.reshape(h.shape[0], -1)


def relu2(h, w_up, w_down, cast: Cast):
    return _mm("lf,fd->ld", jnp.square(jax.nn.relu(_mm("ld,df->lf", h, w_up, cast))),
               w_down, cast)


def routed(s: Dict[str, Any], w, h, cast: Cast, first_held: int = 0):
    """The held experts' part, unscaled, back at full width."""
    held = w["w_up"].shape[0]

    def rows(hb):
        scores = jax.nn.sigmoid(_mm("ld,de->le", hb, w["router"], None))  # never cast
        _, chosen = jax.lax.top_k(scores + w["router_bias"], s["experts_per_token"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / picked.sum(-1, keepdims=True)
        latent = _mm("ld,dc->lc", hb, w["latent_down"], cast)

        def one(acc, args):
            e, wu, wd = args
            mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
            return acc + mine[:, None] * relu2(latent, wu, wd, cast), None

        acc, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                              (first_held + jnp.arange(held), w["w_up"], w["w_down"]))
        return _mm("lc,cd->ld", acc, w["latent_up"], cast)

    return _by_rows(rows, h)


def expert_layer(s: Dict[str, Any], w, h, cast: Cast, k, first_held: int = 0):
    shared = _by_rows(lambda hb: relu2(hb, w["shared_up"], w["shared_down"], cast), h)
    return shared + k["routed_scale"] * routed(s, w, h, cast, first_held)


def block(s: Dict[str, Any], w, x, k, *, kind: str, cast: Cast = None, first_held: int = 0):
    """One layer (``kind`` ``M``, ``E`` or ``*``) over one sequence x [L, d];
    ``w``: that layer's canonical arrays; ``k``: :func:`knobs`."""
    h = rms_norm(x, w["norm"], s["norm_eps"])
    if kind == "M":
        return x + mamba(s, w, h, cast, k)
    if kind == "*":
        return x + attention(s, w, h, cast)
    return x + expert_layer(s, w, h, cast, k, first_held)


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


@functools.partial(jax.jit, static_argnames=("fs", "kind", "cast"))
def block_jit(fs, w, x, k, *, kind, cast=None):
    """``block`` with the fault's knobs ``k`` (:func:`knobs`) as arrays."""
    return block(dict(fs), w, x, k, kind=kind, cast=cast)
