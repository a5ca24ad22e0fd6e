"""Plain float32 ``jax.numpy`` reference of the SDAR-MoE language model's
forward pass under the block mask, and of its generation by diffusion over
blocks, at the cut the configuration states. Imports nothing of the
program. No cache, no kernels, no batching: one sequence, one layer at a
time (the caller makes each layer's weights from the seed, ``weights_sdar``,
and drops them before the next), attention scores in blocks of query rows.
Every matmul runs at ``highest`` precision unless a lower-precision ``cast``
is given (the control: operands rounded before every matmul).

Equations (x: the residual stream, float32; per token t; block length B):
  RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g.
  h = RMSNorm(x; g1); q = Wq h [H, d], k = Wk h [K, d], v = Wv h [K, d], no
  bias; q = RMSNorm_d(q; gq), k = RMSNorm_d(k; gk) over each head's d dims
  (one gain vector a layer, shared by the heads), THEN rotary (half-split
  over all d dims, theta) at position t; query head i reads KV head
  i // (H / K); query t sees key j iff j // B <= t // B (every earlier
  block, and ALL of its own); p = softmax_j(q_t . k_j / sqrt(d));
  x <- x + Wo concat_i(p v).
  h2 = RMSNorm(x; g2); r = softmax(Wr h2) over ALL experts (float32, never
  cast); S = the k largest; w_e = r_e / sum_{e' in S} r_e';
  x <- x + sum_{e in S, held} w_e Wdown_e (silu(Wgate_e h2) * (Wup_e h2)).
  After the last layer RMSNorm(x; gf), then the untied head. The logits at a
  position are of THAT position's own token (no shift by one).

Generation (``block_diffusion_generate`` of the release, remasking strategy
``low_confidence_dynamic``; :func:`generate`): a prompt of P tokens leaves
``B * (P // B)`` of them as whole blocks; the tail opens the first block,
the rest of which is mask ids. While a block has a masked position: a
forward pass over everything so far and the block (mask ids where masked);
at each masked position the candidate x0 (argmax at temperature 0) and its
confidence softmax(logits)[x0]; reveal every masked position whose
confidence exceeds the threshold if those are at least n = B / steps, else
the n most confident (the earlier position on a tie). When none is masked
the block is final (the program runs one more pass over it to leave its
keys and values in its cache; here nothing is cached, so every later pass
sees the final ids).

What the served tokens are held to (:func:`two_streams`): the served ids and
the pass that revealed each give back every pass's input. The FINAL stream
is the whole sequence with its final ids; a VARIANT is one block at one
denoising pass (mask ids where the pass saw them), whose rows see the final
stream's keys of every earlier block and the variant's own. Both streams go
through a layer together.

``fault`` computes a WRONG model on purpose, for the readings the cell's
limits are set from: ``causal_in_block`` (no sight of later positions of
the own block), ``no_commit`` (a generated block's keys and values left as
its last denoising pass wrote them, one position still masked),
``top7`` (one expert fewer chosen), ``no_qk_norm``. ``left_to_right``
(reveal by position, not by confidence) is a fault of the CHOICE and lives
in :func:`generate` and in the runner's reading.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Cast = Optional[Callable[[jax.Array], jax.Array]]
QUERY_BLOCK = 512


def _round_to(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x), initial=0.0), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fp8_cast(x: jax.Array) -> jax.Array:
    """The control's rounding: float8 e4m3, one scale a tensor."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def bf16_cast(x: jax.Array) -> jax.Array:
    """The precision the configuration states, for the tests' tolerance."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(spec: str, a, b, cast: Cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta: float):
    """x [..., L, heads, dim] at ``positions`` [..., L]; rotates (first
    half, second half) of every dim."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(s, w, h, positions, cast: Cast, fault):
    """h [..., L, d] -> q [..., L, H, d], k, v [..., L, H, d] (the KV heads
    repeated to the query heads: head i <- i // group)."""
    q = _mm("...ld,dhk->...lhk", h, w["wq"], cast)
    k = _mm("...ld,dhk->...lhk", h, w["wk"], cast)
    v = _mm("...ld,dhk->...lhk", h, w["wv"], cast)
    if fault != "no_qk_norm":
        q = rms_norm(q, w["norm_q"], s["norm_eps"])
        k = rms_norm(k, w["norm_k"], s["norm_eps"])
    q, k = rope(q, positions, s["rope_theta"]), rope(k, positions, s["rope_theta"])
    group = q.shape[-2] // k.shape[-2]
    return q, jnp.repeat(k, group, axis=-2), jnp.repeat(v, group, axis=-2)


def _sees(s, t, j, fault):
    """Query position t sees key position j (broadcast)."""
    B = s["block_len"]
    if fault == "causal_in_block":
        return j <= t
    return j // B <= t // B


def swiglu(h, w_gate, w_up, w_down, cast: Cast):
    return _mm("lf,fd->ld", jax.nn.silu(_mm("ld,df->lf", h, w_gate, cast))
               * _mm("ld,df->lf", h, w_up, cast), w_down, cast)


def expert_layer(s: Dict[str, Any], w, h, cast: Cast, fault: Optional[str],
                 first_held: int = 0):
    """h [L, d] -> the held experts' part of the layer's output."""
    k = s["experts_per_token"] - (1 if fault == "top7" else 0)
    probs = jax.nn.softmax(_mm("ld,de->le", h, w["router"], None), axis=-1)  # never cast
    picked, chosen = jax.lax.top_k(probs, k)
    weight = picked / picked.sum(-1, keepdims=True)

    def one(acc, args):
        e, wg, wu, wd = args
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)  # [L]; 0: not chosen
        return acc + mine[:, None] * swiglu(h, wg, wu, wd, cast), None

    held = w["w_gate"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (first_held + jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return acc


def two_streams(s: Dict[str, Any], w, x, xv, var_block, stale=None, *, cast: Cast = None,
                fault: Optional[str] = None):
    """One layer over one sequence and its variants. ``x`` [L, d]: the FINAL
    stream (L whole blocks). ``xv`` [V, B, d]: variant ``n`` is block
    ``var_block[n]`` of the sequence at one denoising pass. ``stale`` [L]
    (``no_commit`` only): for each position the flat row of ``xv`` whose key
    and value stand in the cache instead of the final stream's (-1: the
    final stream's own). Returns the two streams after the layer."""
    L, B, d = x.shape[0], s["block_len"], s["head_dim"]
    V = xv.shape[0]
    pos = jnp.arange(L)
    vpos = var_block[:, None] * B + jnp.arange(B)                     # [V, B]
    q, k, v = _qkv(s, w, rms_norm(x, w["norm_attn"], s["norm_eps"]), pos, cast, fault)
    qv, kv, vv = _qkv(s, w, rms_norm(xv, w["norm_attn"], s["norm_eps"]), vpos, cast, fault)
    if fault == "no_commit" and stale is not None:
        # what later blocks read of a generated block: its last denoising
        # pass's keys and values (the final stream's generated rows are then
        # read by nobody: only its prompt rows, which a prefill wrote, count)
        flat_k, flat_v = kv.reshape(V * B, *kv.shape[2:]), vv.reshape(V * B, *vv.shape[2:])
        swap = (stale >= 0)[:, None, None]
        k_seen = jnp.where(swap, flat_k[jnp.maximum(stale, 0)], k)
        v_seen = jnp.where(swap, flat_v[jnp.maximum(stale, 0)], v)
    else:
        k_seen, v_seen = k, v
    scale = d ** -0.5
    block = min(L, QUERY_BLOCK)
    if L % block:
        raise ValueError(f"{L} positions are not whole query blocks of {block}")

    def rows(args):
        qb, t = args                                                  # [block, H, d], [block]
        logits = _mm("qhk,jhk->hqj", qb, k, cast) * scale
        mask = _sees(s, t[:, None], pos[None, :], fault)
        p = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
        return _mm("hqj,jhd->qhd", p, v, cast)

    ctx = jax.lax.map(rows, (q.reshape(L // block, block, *q.shape[1:]),
                             pos.reshape(L // block, block)))
    x = x + _mm("lhd,hdm->lm", ctx.reshape(L, *ctx.shape[2:]), w["wo"], cast)

    # a variant's rows: the cache's keys of every EARLIER block, and its own
    before = pos[None, None, :] // B < var_block[:, None, None]        # [V, 1, L]
    far = _mm("nqhk,jhk->nhqj", qv, k_seen, cast) * scale             # [V, H, B, L]
    far = jnp.where(before[:, None], far, -jnp.inf)
    near = _mm("nqhk,njhk->nhqj", qv, kv, cast) * scale               # [V, H, B, B]
    inside = _sees(s, vpos[:, :, None], vpos[:, None, :], fault)
    near = jnp.where(inside[:, None], near, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([far, near], axis=-1), axis=-1)
    ctxv = _mm("nhqj,jhd->nqhd", p[..., :L], v_seen, cast) \
        + _mm("nhqj,njhd->nqhd", p[..., L:], vv, cast)
    xv = xv + _mm("nqhd,hdm->nqm", ctxv, w["wo"], cast)

    both = jnp.concatenate([x, xv.reshape(V * B, x.shape[1])])
    both = both + expert_layer(s, w, rms_norm(both, w["norm_ffn"], s["norm_eps"]), cast, fault)
    return both[:L], both[L:].reshape(V, B, x.shape[1])


def block(s: Dict[str, Any], w, x, *, cast: Cast = None, fault: Optional[str] = None):
    """One layer over one sequence x [L, d] of whole blocks under the block
    mask; ``w``: that layer's canonical arrays."""
    none = jnp.zeros((0, s["block_len"], x.shape[1]), x.dtype)
    return two_streams(s, w, x, none, jnp.zeros((0,), jnp.int32), cast=cast, fault=fault)[0]


def logits_at(s: Dict[str, Any], top, h, cast: Cast = None):
    return _mm("...d,dv->...v", rms_norm(h, top["norm_final"], s["norm_eps"]),
               top["head"], cast)


def gaps_under_best(ref_logits, tokens):
    """How far each token's reference logit lies under the reference's best
    at its position, in standard deviations of that position's logits."""
    best = ref_logits.max(-1)
    pick = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return (best - pick) / ref_logits.std(-1)


def confidence(logits):
    """(the best id, the log of its share of the softmax) of each row."""
    return jnp.argmax(logits, axis=-1), logits.max(-1) - jax.nn.logsumexp(logits, axis=-1)


def frozen(s: Dict[str, Any]):
    """``s`` as something ``jax.jit`` can take as a static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in s.items()))


@functools.partial(jax.jit, static_argnames=("fs", "cast", "fault"))
def two_streams_jit(fs, w, x, xv, var_block, stale, *, cast=None, fault=None):
    return two_streams(dict(fs), w, x, xv, var_block, stale, cast=cast, fault=fault)


# -- a served sequence back into every pass's input ------------------------------

def passes_of(s: Dict[str, Any], prompt: Sequence[int], tokens: Sequence[int],
              marks: Sequence[int]) -> Dict[str, np.ndarray]:
    """What every denoising pass of a served request saw, from its prompt,
    its ``tokens`` and beside each the pass of its block that revealed it
    (``marks``, 1-based). ``final`` [L]: the sequence's whole blocks with
    their final ids (the last block filled with mask ids past the served
    tokens, which nothing is held to). ``ids`` [V, B]: a variant a
    generated block and pass, mask ids where that pass still saw them;
    ``block`` [V], ``step`` [V] (1-based); ``shown`` [V, B]: the positions
    that pass revealed; ``masked`` [V, B]: what it saw masked (of a last
    block served in part only the first pass is known); ``stale``
    [L]: for each generated position the flat row of ``ids`` of its block's
    LAST denoising pass (-1: a prompt position)."""
    B, mask_id = s["block_len"], s["mask_id"]
    P, n = len(prompt), len(tokens)
    first = P // B                                  # the block the tail opens
    blocks = -(-(P + n) // B)
    final = np.full((blocks * B,), mask_id, np.int64)
    final[:P], final[P:P + n] = prompt, tokens
    mark = np.zeros((blocks * B,), np.int64)        # 0: a prompt's; past the served: never
    mark[P:P + n] = marks
    mark[P + n:] = 1 << 30
    out: Dict[str, List[Any]] = {k: [] for k in ("ids", "block", "step", "shown", "masked")}
    stale = np.full((blocks * B,), -1, np.int64)
    for b in range(first, blocks):
        at = slice(b * B, b * B + B)
        ours = mark[at]
        served = ours[(ours > 0) & (ours < 1 << 30)]
        steps = int(served.max(initial=0))
        if (ours == 1 << 30).any():
            # the request's last block, served in part: what its later
            # passes saw of the positions never served is not known
            steps = min(steps, 1)
        for step in range(1, steps + 1):
            hidden = ours >= step
            out["ids"].append(np.where(hidden, mask_id, final[at]))
            out["block"].append(b)
            out["step"].append(step)
            out["masked"].append(hidden)
            out["shown"].append(ours == step)
        if len(served) and not (ours == 1 << 30).any():
            # the whole block was served: its last pass's rows
            stale[at] = (len(out["ids"]) - 1) * B + np.arange(B)
    shape = {"ids": (0, B), "shown": (0, B), "masked": (0, B)}
    made = {k: (np.asarray(v) if v else np.zeros(shape.get(k, (0,)), np.int64))
            for k, v in out.items()}
    made["block"] = made["block"].astype(np.int64)
    return {"final": final, "stale": stale, **made}


# -- generation, naively: every pass a whole forward ---------------------------------

def forward(s: Dict[str, Any], layers, top, ids, *, cast: Cast = None,
            fault: Optional[str] = None):
    """ids [L] (whole blocks) -> logits [L, vocab] under the block mask."""
    x = top["embedding"][jnp.asarray(ids)]
    for w in layers:
        x = block(s, w, x, cast=cast, fault=fault)
    return logits_at(s, top, x, cast)


def choose_reveal(s: Dict[str, Any], conf: np.ndarray, masked: np.ndarray,
                  left_to_right: bool = False) -> np.ndarray:
    """Which masked positions of one block a denoising pass reveals [B]."""
    quota = s["block_len"] // s["denoise_steps"]
    where = np.flatnonzero(masked)
    high = masked & (conf > s["confidence_threshold"])
    if left_to_right:
        order = where
    else:
        if high.sum() >= quota:
            return high
        order = where[np.argsort(-conf[where], kind="stable")]
    shown = np.zeros_like(masked)
    shown[order[:quota]] = True
    return shown


def generate(s: Dict[str, Any], layers, top, prompt: Sequence[int], new_tokens: int, *,
             cast: Cast = None, fault: Optional[str] = None
             ) -> Tuple[List[int], List[int]]:
    """``new_tokens`` tokens after ``prompt`` at temperature 0, and beside
    each the pass of its block that revealed it (1-based). Every pass is a
    whole uncached forward over the sequence so far."""
    B, mask_id = s["block_len"], s["mask_id"]
    seq = list(int(t) for t in prompt)
    P = len(seq)
    marks: List[int] = []
    while len(seq) - P < new_tokens:
        start = len(seq) // B * B
        ids = np.asarray(seq[start:] + [mask_id] * (B - len(seq) + start))
        masked = np.arange(B) >= len(seq) - start
        shown_at = np.zeros((B,), np.int64)
        step = 0
        while masked.any():
            step += 1
            logits = forward(s, layers, top, np.concatenate([seq[:start], ids]).astype(np.int64),
                             cast=cast, fault=fault)[start:]
            x0 = np.asarray(jnp.argmax(logits, -1))
            conf = np.asarray(jnp.exp(confidence(logits)[1]))
            shown = choose_reveal(s, conf, masked, fault == "left_to_right")
            ids = np.where(shown, x0, ids)
            shown_at = np.where(shown, step, shown_at)
            masked = masked & ~shown
        marks += [int(m) for m in shown_at[len(seq) - start:]]
        seq = seq[:start] + [int(t) for t in ids]
    return seq[P:P + new_tokens], marks[:new_tokens]
