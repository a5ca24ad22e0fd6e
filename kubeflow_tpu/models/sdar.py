"""SDAR-style block-diffusion decoder for the serving path: grouped-query
attention that is causal BETWEEN blocks and full INSIDE a block, per-head
RMSNorm on queries and keys, a softmax-routed expert layer, and generation
by unmasking a block of ``block_len`` positions over several forward
passes.

Plain functions over a parameter tree, as ``models/mimo.py``: the serving
engine needs two device programs of it, and both write straight into the
paged arenas:

- :func:`prefill_chunk` — one fixed-size chunk of ONE prompt under the block
  mask. It yields no token: a prompt's first token comes out of its first
  block.
- :func:`block_pass` — one forward pass for every slot over the
  ``block_len`` positions of the block it is working on, followed per slot
  by a REVEAL (some of its positions were still masked) or a COMMIT (none
  was: the block's tokens are handed out, the cursor passes the block and
  the next block of mask ids is loaded).

Layer equations (``benchmark/reference/sdar.py`` is the plain float32
reading of the same), per token ``t``, block length ``B``: RMSNorm; q, k, v
projections without bias; RMSNorm over each head's dims of q and of k (one
gain vector a layer, shared by the heads), THEN rotary (half-split, every
dim); query ``t`` sees key ``j`` iff ``j // B <= t // B``; softmax router
over ALL experts in float32, the ``k`` largest, weights normalised over the
chosen; SwiGLU experts; final RMSNorm; untied head. The logits at a masked
position are of that position's own token. Parameters and matmul operands
are bfloat16; the residual stream, the norms' statistics, the router, the
softmaxes, the logits and the confidences are float32.

Generation (the release's ``block_diffusion_generate``,
``low_confidence_dynamic``): while a block has a masked position, a pass
over its ``B`` positions (mask ids where masked) against the committed
cache gives at each masked position a candidate (argmax at temperature 0,
else a sample) and its confidence ``softmax(logits)[candidate]``; every
masked position whose confidence exceeds the threshold is revealed if
those are at least the step's quota ``B / steps``, else the quota's most
confident. When none is masked, one more pass over the final ids leaves
the block's keys and values in the cache: the commit.

One kind of cache (``serving/paged.py``): the block table's append-only
kind. The positions ``cursor .. cursor + B - 1`` of the block in flight are
written at EVERY pass (overwriting the last pass's) and read up to ``cursor
+ B``; the cursor moves by whole blocks and only at a commit. ``B`` divides
the page, so a block never straddles one. Per-slot block state rides in the
cache beside the arenas: the block's ids, which are masked, the pass that
revealed each, the passes so far, and how many of the first block's
positions were the prompt's tail.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.chunk_attention import chunk_attention
from ..ops.head_choice import head_choice
from ..ops.paged_attention import paged_decode_attention
from ..parallel.moe import held_experts_ffn, softmax_top_k
from .mimo import _heads_apart, partial_rope, rms_norm

#: counters a pass and a prefill chunk return beside their results:
#: assignments on held experts, the busiest held expert's, held experts
#: touched (``parallel/moe.held_experts_ffn``); a live slot's denoising
#: passes and commit passes; blocks committed; tokens revealed; pages of the
#: cache the attention read, a layer; passes that chose from logits written
#: out because a live slot samples (:func:`block_pass`)
STATS = 9


@dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    n_layers: int = 6
    d_ff_expert: int = 768
    n_experts: int = 128            # the router's outputs, all of them
    experts_per_token: int = 8
    held_experts: int = 128         # how many of them live on this chip
    first_held_expert: int = 0
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq: int = 4096
    block_len: int = 4
    denoise_steps: int = 4
    confidence_threshold: float = 0.9
    mask_id: int = 151669
    dtype: Any = jnp.bfloat16

    @property
    def reveal_quota(self) -> int:
        """Positions a denoising pass reveals at least."""
        return self.block_len // self.denoise_steps

    @classmethod
    def tiny(cls) -> "SdarConfig":
        return cls(vocab_size=96, d_model=64, n_heads=8, kv_heads=2, head_dim=16,
                   n_layers=2, d_ff_expert=32, n_experts=16, experts_per_token=4,
                   held_experts=16, max_seq=128, mask_id=95)


def init_params(cfg: SdarConfig, key: jax.Array) -> Dict[str, Any]:
    """Random weights in the tree the programs read: matrices N(0, 0.02),
    gains 1, the router float32."""
    d, dt, f, n = cfg.d_model, cfg.dtype, cfg.d_ff_expert, cfg.held_experts
    keys = iter(jax.random.split(key, 8 * cfg.n_layers + 2))

    def mat(*shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dtype)

    layers = [{
        "norm_attn": jnp.ones((d,), dt), "norm_ffn": jnp.ones((d,), dt),
        "norm_q": jnp.ones((cfg.head_dim,), dt), "norm_k": jnp.ones((cfg.head_dim,), dt),
        "wq": mat(d, cfg.n_heads, cfg.head_dim), "wk": mat(d, cfg.kv_heads, cfg.head_dim),
        "wv": mat(d, cfg.kv_heads, cfg.head_dim), "wo": mat(cfg.n_heads, cfg.head_dim, d),
        "moe": {"router": mat(d, cfg.n_experts, dtype=jnp.float32),
                "w_gate": mat(n, d, f), "w_up": mat(n, d, f), "w_down": mat(n, f, d)},
    } for _ in range(cfg.n_layers)]
    return {"embedding": mat(cfg.vocab_size, d), "head": mat(d, cfg.vocab_size),
            "norm_final": jnp.ones((d,), dt), "layers": layers}


def fresh_cache(cfg: SdarConfig, slots: int, blocks: int, block_t: int) -> Dict[str, Any]:
    """One arena a layer (``blocks`` allocatable blocks plus the trash
    block, ``[blocks + 1, block_t, kv_heads * head_dim]``: a position's KV
    heads side by side in one row) and, a slot, the cursor and the state of
    the block in flight. A fresh slot is committing nothing: every position
    masked, which a dead row stays."""
    if block_t % cfg.block_len:
        raise ValueError(f"a block of {cfg.block_len} must divide the page of {block_t}")
    B, rows = cfg.block_len, (blocks + 1, block_t, cfg.kv_heads * cfg.head_dim)
    cache: Dict[str, Any] = {
        "cursors": jnp.zeros((slots,), jnp.int32),
        "block_ids": jnp.full((slots, B), cfg.mask_id, jnp.int32),
        "masked": jnp.ones((slots, B), bool),
        "revealed_at": jnp.zeros((slots, B), jnp.int32),
        "passes": jnp.zeros((slots,), jnp.int32),
        "skip": jnp.zeros((slots,), jnp.int32),
    }
    for i in range(cfg.n_layers):
        cache[f"layer_{i}"] = {"k": jnp.zeros(rows, cfg.dtype), "v": jnp.zeros(rows, cfg.dtype)}
    return cache


# -- pieces ---------------------------------------------------------------------

def _qkv(cfg: SdarConfig, layer: Dict[str, Any], h: jax.Array, positions: jax.Array
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h [T, d] float32 at ``positions`` [T] -> q [T, heads, dim], k, v [T,
    kv_heads, dim] in the compute type: q and k normalised over each head's
    dims, then rotated."""
    h = h.astype(cfg.dtype)
    q = jnp.einsum("td,dhk->thk", h, layer["wq"])
    k = jnp.einsum("td,dhk->thk", h, layer["wk"])
    v = jnp.einsum("td,dhk->thk", h, layer["wv"])
    q = rms_norm(q, layer["norm_q"], cfg.norm_eps).astype(cfg.dtype)
    k = rms_norm(k, layer["norm_k"], cfg.norm_eps).astype(cfg.dtype)
    return (partial_rope(q, positions, cfg.rope_theta, cfg.head_dim),
            partial_rope(k, positions, cfg.rope_theta, cfg.head_dim), v)


def _experts(cfg: SdarConfig, layer: Dict[str, Any], h: jax.Array, live: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """h: the normalised residual stream, float32 [T, d]. Returns (the
    layer's output in the compute type, expert stats int32 [3])."""
    p = layer["moe"]
    with jax.named_scope("moe_router"):
        idx, w = softmax_top_k(h, p["router"], cfg.experts_per_token)
    return held_experts_ffn(h.astype(cfg.dtype), idx, w, p["w_gate"], p["w_up"], p["w_down"],
                            first_held=cfg.first_held_expert, live=live)


def _final_norm(cfg: SdarConfig, params, x: jax.Array) -> jax.Array:
    """The residual stream after the last layer, float32 [T, d], as the
    head's operand in the compute type."""
    with jax.named_scope("lm_head"):
        return rms_norm(x, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)


def _head(params, last: jax.Array) -> jax.Array:
    """Float32 logits [T, vocab], written out."""
    with jax.named_scope("lm_head"):
        return jnp.dot(last, params["head"], preferred_element_type=jnp.float32)


# -- a pass: one block position set for every slot ---------------------------------

def _block_attention(cfg: SdarConfig, layer, arena, h, cursors, table, live, trash: int):
    """h [S * B, d], slot ``s``'s rows at positions ``cursors[s] .. + B -
    1``. Writes the block's keys and values into the slot's page (over the
    last pass's) and attends over ``[0, cursor + B)`` with no mask inside
    the block: ``B x heads`` query rows a slot through
    ``ops.paged_attention``, a KV head's ``B x group`` rows together."""
    S, B, kv = cursors.shape[0], cfg.block_len, cfg.kv_heads
    g, hd = cfg.n_heads // kv, cfg.head_dim
    bt, width = arena["k"].shape[1], table.shape[1]
    positions = (cursors[:, None] + jnp.arange(B)).reshape(-1)
    q, k, v = _qkv(cfg, layer, h, positions)
    with jax.named_scope("attn_block"):
        with jax.named_scope("kv_write"):
            block = cursors // bt
            # a row past the columns it was handed (dead, or past its
            # budget) writes to trash
            ids = jnp.where(block < width, jnp.take_along_axis(
                table, jnp.minimum(block, width - 1)[:, None], axis=1)[:, 0], trash)
            at = ids[:, None], jnp.mod(cursors, bt)[:, None] + jnp.arange(B)
            keys = arena["k"].at[at].set(k.reshape(S, B, -1))
            vals = arena["v"].at[at].set(v.reshape(S, B, -1))
        rows = jnp.swapaxes(q.reshape(S, B, kv, g, hd), 1, 2).reshape(S, kv, B * g, hd)
        ctx = paged_decode_attention(
            _heads_apart(rows, kv), keys, vals, table, jnp.where(live, cursors + B, 0),
            scale=hd ** -0.5, kv_heads=kv)                          # [S, kv * B * g, hd]
        ctx = jnp.swapaxes(ctx.reshape(S, kv, B, g, hd), 1, 2).astype(cfg.dtype)
    out = jnp.einsum("thd,hdm->tm", ctx.reshape(S * B, cfg.n_heads, hd), layer["wo"])
    return out, {"k": keys, "v": vals}


def choose(cfg: SdarConfig, logits: jax.Array, temps: jax.Array, keys: jax.Array
           ) -> Tuple[jax.Array, jax.Array]:
    """logits [S * B, vocab] float32 (slot ``s``'s block in rows ``s * B ..``)
    -> (candidate ids [S, B] int32, their confidences [S, B] float32): the
    argmax where a slot's temperature is 0, else a sample on the slot's key
    (position ``j`` on ``fold_in(key, j)``); the confidence is the
    candidate's share of the softmax (of the tempered logits where sampled).
    The rows stay rows to the end: every reduction runs over the last axis
    as the head wrote it (a ``[S, B, vocab]`` view of them is a relayout on
    the chip, 4 rows in a tile of 8)."""
    B = cfg.block_len
    S = logits.shape[0] // B
    hot = jnp.repeat(temps > 0.0, B)
    lg = jnp.where(hot[:, None], logits / jnp.repeat(jnp.maximum(temps, 1e-6), B)[:, None],
                   logits)
    each = jax.vmap(lambda key: jax.vmap(lambda j: jax.random.fold_in(key, j))(jnp.arange(B)))(keys)
    drawn = jax.vmap(jax.random.categorical)(each.reshape(S * B, -1), lg)
    x0 = jnp.where(hot, drawn, jnp.argmax(logits, axis=-1)).astype(jnp.int32)
    conf = jnp.exp(jnp.take_along_axis(lg, x0[:, None], axis=-1)[:, 0]
                   - jax.nn.logsumexp(lg, axis=-1))
    return x0.reshape(S, B), conf.reshape(S, B)


def reveal(cfg: SdarConfig, masked: jax.Array, conf: jax.Array) -> jax.Array:
    """Which masked positions a denoising pass reveals, [S, B] bool: all
    whose confidence exceeds the threshold if those are at least the quota,
    else the quota's most confident (the earlier position on a tie)."""
    B = masked.shape[1]
    conf = jnp.where(masked, conf, -jnp.inf)
    high = masked & (conf > cfg.confidence_threshold)
    j = jnp.arange(B)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (j[None, None, :] < j[None, :, None]))
    most = masked & (jnp.sum(ahead, axis=-1) < cfg.reveal_quota)
    enough = jnp.sum(high, axis=-1, keepdims=True) >= cfg.reveal_quota
    return jnp.where(enough, high, most)


def block_layers(cfg: SdarConfig, params, cache, table: jax.Array, trash: int):
    """Every slot's block (``cache["block_ids"]`` at ``cursors .. + B - 1``)
    through the layers, its keys and values written into the slot's page.
    Returns (the residual stream after the last layer [S * B, d] float32,
    the cache with the arenas written, live [S] bool, expert stats int32
    [3])."""
    B = cfg.block_len
    cursors = cache["cursors"]
    live = table[:, 0] != trash
    row_live = jnp.repeat(live, B)
    x = params["embedding"][cache["block_ids"].reshape(-1)].astype(jnp.float32)
    out_cache = dict(cache)
    moe = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _block_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            cursors, table, live, trash)
        x = x + a
        f, st = _experts(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), row_live)
        x = x + f
        moe = moe + st
    return x, out_cache, live, moe


def block_logits(cfg: SdarConfig, params, cache, table: jax.Array, trash: int):
    """The forward pass of :func:`block_pass` alone, its logits written out:
    (logits [S, B, vocab] float32, the cache with the arenas written, live
    [S] bool, expert stats int32 [3])."""
    x, out_cache, live, moe = block_layers(cfg, params, cache, table, trash)
    logits = _head(params, _final_norm(cfg, params, x))
    return logits.reshape(-1, cfg.block_len, cfg.vocab_size), out_cache, live, moe


def block_pass(cfg: SdarConfig, params, cache, table: jax.Array, temps: jax.Array,
               keys: jax.Array, trash: int):
    """One forward pass for every slot over the block it is working on, then
    its reveal or its commit. ``table`` [S, view] (the block table's first
    columns; a row whose first column is trash belongs to no request: it
    reads nothing, takes no expert and keeps its state), ``keys`` [S, 2]
    this pass's sampling keys. While no live slot samples, the candidate and
    its confidence come straight out of the head (``ops.head_choice``: no
    logits are written); a draw wants them all, so a pass with a sampling
    slot writes them out and chooses from them (:func:`choose`). Returns
    (cache, committed [S] bool, the committed blocks' ids [S, B] and the
    pass that revealed each position [S, B] (0: it was the prompt's), how
    many of a committed block's first positions were the prompt's [S],
    stats int32 [STATS])."""
    B = cfg.block_len
    cursors, ids, masked = cache["cursors"], cache["block_ids"], cache["masked"]
    x, out_cache, live, moe = block_layers(cfg, params, cache, table, trash)
    last = _final_norm(cfg, params, x)

    def streamed(last):
        with jax.named_scope("lm_head"):
            best, top, lse = head_choice(last, params["head"])
            return best.reshape(-1, B), jnp.exp(top - lse).reshape(-1, B)

    def sampled(last):
        logits = _head(params, last)
        with jax.named_scope("unmask"):
            return choose(cfg, logits, temps, keys)

    # the draw is 151,936 random numbers a row and 155 MB of logits: skipped
    # whole where no slot samples
    sampling = jnp.any(live & (temps > 0.0))
    x0, conf = jax.lax.cond(sampling, sampled, streamed, last)
    with jax.named_scope("unmask"):
        commit = live & ~jnp.any(masked, axis=-1)
        denoise = live & ~commit
        shown = reveal(cfg, masked, conf) & denoise[:, None]
        passes = cache["passes"] + denoise
        fresh = commit[:, None]
        out_cache.update(
            cursors=cursors + B * commit,
            block_ids=jnp.where(fresh, cfg.mask_id, jnp.where(shown, x0, ids)),
            masked=jnp.where(fresh, True, masked & ~shown),
            revealed_at=jnp.where(fresh, 0, jnp.where(shown, passes[:, None],
                                                      cache["revealed_at"])),
            passes=jnp.where(commit, 0, passes),
            skip=jnp.where(commit, 0, cache["skip"]))
    bt = cache["layer_0"]["k"].shape[1]
    pages = jnp.sum(jnp.where(live, -(-(cursors + B) // bt), 0), dtype=jnp.int32)
    count = lambda what: jnp.sum(what, dtype=jnp.int32)
    stats = jnp.concatenate([moe, jnp.stack([
        count(denoise), count(commit), count(commit), count(shown), pages,
        count(sampling)])])
    return out_cache, commit, ids, cache["revealed_at"], cache["skip"], stats


# -- prefill: one chunk of one prompt -------------------------------------------

def _chunk_attention(cfg: SdarConfig, layer, arena, h, start, read, write):
    """h [C, d] at positions ``start + i``: the chunk's keys and values go
    into the blocks ``write`` names, then the queries read the row's view
    ``read`` under the block mask. ``ops.chunk_attention`` masks by position
    (a query sees the keys at or before its own), so each query rides at the
    LAST position of its block."""
    C, kv, hd, B = h.shape[0], cfg.kv_heads, cfg.head_dim, cfg.block_len
    g = cfg.n_heads // kv
    bt, view = arena["k"].shape[1], read.shape[0]
    positions = start + jnp.arange(C)
    q, k, v = _qkv(cfg, layer, h, positions)
    with jax.named_scope("attn_block"):
        with jax.named_scope("kv_write"):
            keys_arena = arena["k"].at[write].set(k.reshape(C // bt, bt, -1))
            vals_arena = arena["v"].at[write].set(v.reshape(C // bt, bt, -1))
        keys = keys_arena[read].reshape(view * bt, kv, hd)
        vals = vals_arena[read].reshape(view * bt, kv, hd)
        rows = jnp.swapaxes(q.reshape(C, kv, g, hd), 0, 1).reshape(kv, C * g, hd)
        ctx = chunk_attention(
            rows, jnp.swapaxes(keys, 0, 1), jnp.swapaxes(vals, 0, 1),
            jnp.repeat(positions // B * B + B - 1, g), jnp.arange(view * bt),
            scale=hd ** -0.5)
        ctx = jnp.swapaxes(ctx.reshape(kv, C, g, hd), 0, 1)
    out = jnp.einsum("thd,hdm->tm", ctx.reshape(C, cfg.n_heads, hd), layer["wo"])
    return out, {"k": keys_arena, "v": vals_arena}


def prefill_chunk(cfg: SdarConfig, params, cache, ids: jax.Array, start, n_valid,
                  read, write):
    """One chunk of one prompt: ``ids`` [C] at positions ``start ..`` (C and
    ``start`` whole blocks), of which the first ``n_valid`` are real.
    ``read`` [view] is the row's block table (its first columns, this
    chunk's blocks included), ``write`` [C / block_t] names the arena block
    each block of the chunk goes to (trash: not kept). Only the prompt's
    WHOLE blocks are prefilled: what the rows past them write is overwritten
    by the first block's passes before anything reads it, and they take no
    expert. No head: a prefill yields no token. Returns (the ids of the
    block the prompt's tail opens [B]: the tail, then mask ids; cache;
    stats int32 [STATS], the expert counters and zeros)."""
    C, B = ids.shape[0], cfg.block_len
    if C % B:
        raise ValueError(f"a prefill chunk of {C} is not whole blocks of {B}")
    whole = (start + n_valid) // B * B - start
    live = jnp.arange(C) < whole
    x = params["embedding"][ids].astype(jnp.float32)
    out_cache = dict(cache)
    moe = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _chunk_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            start, read, write)
        x = x + a
        f, st = _experts(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), live)
        x = x + f
        moe = moe + st
    tail = jax.lax.dynamic_slice(jnp.pad(ids, (0, B)), (whole,), (B,))
    opening = jnp.where(jnp.arange(B) < n_valid - whole, tail, cfg.mask_id).astype(jnp.int32)
    return opening, out_cache, jnp.concatenate([moe, jnp.zeros((STATS - 3,), jnp.int32)])
