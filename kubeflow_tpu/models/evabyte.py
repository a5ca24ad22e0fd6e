"""EvaByte-style byte-level decoder for the serving path: EVA attention
(exact inside the aligned window, one learned summary a chunk of every
earlier window) over a paged cache of a local kind and a summary kind.

Plain functions over a parameter tree, as ``models/mimo.py``: the serving
engine needs two device programs of it, and both write straight into the
paged arenas:

- :func:`prefill_chunk` — one fixed-size chunk of ONE prompt. Chunks never
  straddle a window (the chunk divides it).
- :func:`decode_step` — one token for every slot, each at its own cursor.

Layer equations (``benchmark/reference/evabyte.py`` is the plain float32
reading of the same), per token ``t``, ``H`` heads of ``d``, window ``W``,
chunk ``C``, ``s = d ** -0.5``: RMSNorm with a unit offset (``x / rms *
(1 + g)``); q, k, v projections; rotary (half-split, every dim) on q and k
at position ``t``; chunk ``c`` (positions ``[C c, C c + C)``) has, once
whole, a summary a head, pooled over its ROTATED keys with the learned
``mu`` and over its values with ``phi``:

    k~_c = sum_j softmax_j(s k_j . mu) k_j     v~_c = sum_j softmax_j(s k_j . phi) v_j

and ``t`` attends, under ONE softmax, to the exact keys of its own window
(``floor(j / W) == floor(t / W)``, ``j <= t``) and to the summaries of
every chunk of every EARLIER window; SwiGLU; untied head. Parameters and
matmul operands are bfloat16; the residual stream, the norms' statistics,
the pooling, the softmax and the logits are float32.

Three device duties a step: write this token's key and value, attend, and
SUMMARISE (pool the chunk a token completes into one row of the summary
arena). Two kinds of cache side by side (``serving/paged.py``): the LOCAL
kind holds a slot's current window only, a ring of ``cols`` blocks in which
logical block ``b`` (positions ``[b * block_t, (b + 1) * block_t)``) sits in
column ``b % cols``, and goes back whole when the cursor leaves the window;
the SUMMARY kind is append-only, a ROW a chunk, read through the first
columns of the slot's block table. A summary is written when its chunk
completes and read only once its whole window has been left: the length the
attention reads is ``(cursor // W) * (W / C)`` rows, not what is written.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.chunk_attention import NOWHERE, chunk_attention
from ..ops.paged_attention import join_softmax, paged_decode_attention
from .mimo import partial_rope

#: pages the decode kernel fetches a group: 256 positions of 16, 2 MB of
#: keys and 2 MB of values a buffer at 32 heads of 128
PAGES_PER_GROUP = 16


@dataclass(frozen=True)
class EvaConfig:
    vocab_size: int = 320
    d_model: int = 4096
    n_heads: int = 32
    head_dim: int = 128
    d_ff: int = 11008
    n_layers: int = 8
    window: int = 2048
    chunk_size: int = 16
    rope_theta: float = 1e5
    norm_eps: float = 1e-5
    max_seq: int = 32768
    dtype: Any = jnp.bfloat16

    @property
    def chunks_per_window(self) -> int:
        return self.window // self.chunk_size

    @classmethod
    def tiny(cls) -> "EvaConfig":
        return cls(vocab_size=96, d_model=64, n_heads=4, head_dim=16, d_ff=128,
                   n_layers=2, window=32, chunk_size=4, max_seq=256)


def pooling_scale(cfg: EvaConfig, logit_sd: float = 0.8) -> float:
    """The scale of ``mu`` and ``phi`` at which a chunk's pooling logits
    ``s k . mu`` have standard deviation ``logit_sd`` under N(0, 0.02)
    projections of a unit-RMS input (a key's entries then have standard
    deviation ``0.02 sqrt(d_model)``): the largest of a chunk's weights is a
    few times their mean, so a program that ignored them would show."""
    return logit_sd / (0.02 * cfg.d_model ** 0.5)


def init_params(cfg: EvaConfig, key: jax.Array) -> Dict[str, Any]:
    """Random weights in the tree the programs read: matrices N(0, 0.02),
    norm gains 0 (unit offset), ``mu`` and ``phi`` N(0, pooling_scale)."""
    d, dt, hd = cfg.d_model, cfg.dtype, cfg.n_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 9 * cfg.n_layers + 2))

    def mat(*shape, std=0.02, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    pool = pooling_scale(cfg)
    layers = [{
        "norm_attn": jnp.zeros((d,), dt), "norm_ffn": jnp.zeros((d,), dt),
        "wq": mat(d, hd), "wk": mat(d, hd), "wv": mat(d, hd), "wo": mat(hd, d),
        "mu": mat(cfg.n_heads, cfg.head_dim, std=pool, dtype=jnp.float32),
        "phi": mat(cfg.n_heads, cfg.head_dim, std=pool, dtype=jnp.float32),
        "mlp": {"w_gate": mat(d, cfg.d_ff), "w_up": mat(d, cfg.d_ff),
                "w_down": mat(cfg.d_ff, d)},
    } for _ in range(cfg.n_layers)]
    return {"embedding": mat(cfg.vocab_size, d), "head": mat(d, cfg.vocab_size),
            "norm_final": jnp.zeros((d,), dt), "layers": layers}


def fresh_cache(cfg: EvaConfig, slots: int, local_blocks: int, summary_blocks: int,
                block_t: int) -> Dict[str, Any]:
    """Arenas of both kinds (the allocatable blocks plus the trash block)
    in every layer and one cursor a slot, shared by the layers. An arena is
    ``[blocks + 1, block_t, heads * head_dim]``: a position's (or a
    chunk's) heads side by side in ONE lane-aligned row, as a token is
    written and as the decode kernel fetches a page."""
    wide = cfg.n_heads * cfg.head_dim
    cache: Dict[str, Any] = {"cursors": jnp.zeros((slots,), jnp.int32)}
    for i in range(cfg.n_layers):
        local, summary = (local_blocks + 1, block_t, wide), (summary_blocks + 1, block_t, wide)
        cache[f"layer_{i}"] = {
            "k": jnp.zeros(local, cfg.dtype), "v": jnp.zeros(local, cfg.dtype),
            "sk": jnp.zeros(summary, cfg.dtype), "sv": jnp.zeros(summary, cfg.dtype)}
    return cache


# -- pieces ---------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """Float32 in, float32 out; the gain is an offset from one."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * (1.0 + gain.astype(jnp.float32))


def _qkv(cfg: EvaConfig, layer: Dict[str, Any], h: jax.Array, positions: jax.Array
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h [T, d] float32 at ``positions`` [T] -> q, k (rotated), v, each
    ``[T, heads, head_dim]`` in the compute type."""
    h = h.astype(cfg.dtype)
    shape = (h.shape[0], cfg.n_heads, cfg.head_dim)
    q, k, v = (jnp.dot(h, layer[w]).reshape(shape) for w in ("wq", "wk", "wv"))
    return (partial_rope(q, positions, cfg.rope_theta, cfg.head_dim),
            partial_rope(k, positions, cfg.rope_theta, cfg.head_dim), v)


def summarise(cfg: EvaConfig, layer: Dict[str, Any], k: jax.Array, v: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """k, v ``[..., C, heads, head_dim]`` (a chunk's rotated keys and its
    values) -> its summary ``[..., heads * head_dim]`` each, in the compute
    type: the keys pooled with ``softmax_j(s k_j . mu)``, the values with
    ``softmax_j(s k_j . phi)``, all in float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    s = cfg.head_dim ** -0.5
    w_k = jax.nn.softmax(s * jnp.einsum("...chd,hd->...ch", kf, layer["mu"]), axis=-2)
    w_v = jax.nn.softmax(s * jnp.einsum("...chd,hd->...ch", kf, layer["phi"]), axis=-2)
    flat = k.shape[:-3] + (cfg.n_heads * cfg.head_dim,)
    return (jnp.einsum("...ch,...chd->...hd", w_k, kf).reshape(flat).astype(cfg.dtype),
            jnp.einsum("...ch,...chd->...hd", w_v, vf).reshape(flat).astype(cfg.dtype))


def _mlp(cfg: EvaConfig, layer: Dict[str, Any], h: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        low, p = h.astype(cfg.dtype), layer["mlp"]
        mid = jax.nn.silu(jnp.dot(low, p["w_gate"])) * jnp.dot(low, p["w_up"])
        return jnp.dot(mid, p["w_down"])


def _head(cfg: EvaConfig, params, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        last = rms_norm(x, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(last, params["head"], preferred_element_type=jnp.float32)


def _heads_apart(q: jax.Array) -> jax.Array:
    """q [S, heads, dim] -> [S, heads, heads * dim], head ``h``'s vector in
    its own block of columns and zeros elsewhere: a product with a row of
    every head's keys side by side then reads the head's own. It costs
    ``heads`` times the multiply-adds of a score product whose one query
    row a head would leave the matrix unit idle anyway."""
    heads = q.shape[1]
    eye = jnp.eye(heads, dtype=q.dtype)
    return (q[:, :, None, :] * eye[None, :, :, None]).reshape(
        q.shape[0], heads, heads * q.shape[2])


def _own(table: jax.Array, blocks: jax.Array) -> jax.Array:
    """Arena blocks of a row's logical ``blocks`` [S, n] in its ring
    ``table`` [S, cols]."""
    return jnp.take_along_axis(table, jnp.mod(blocks, table.shape[1]), axis=1)


# -- decode: one token for every slot ---------------------------------------------

def _decode_attention(cfg: EvaConfig, layer, arena, h, cursors, summary_table,
                      local_table, live, summary_trash: int):
    """h [S, d] at positions ``cursors`` [S]: the three duties of a step.
    Returns (the attention's output [S, d], the layer's arenas)."""
    S, H = h.shape[0], cfg.n_heads
    W, C, bt = cfg.window, cfg.chunk_size, arena["k"].shape[1]
    q, k, v = _qkv(cfg, layer, h, cursors)
    off = jnp.mod(cursors, bt)
    with jax.named_scope("kv_write"):
        ids = _own(local_table, (cursors // bt)[:, None])[:, 0]
        keys = arena["k"].at[ids, off].set(k.reshape(S, -1))
        vals = arena["v"].at[ids, off].set(v.reshape(S, -1))
    with jax.named_scope("attn_eva"):
        qb = _heads_apart(q)
        kernel = dict(scale=cfg.head_dim ** -0.5, kv_heads=H, pages=PAGES_PER_GROUP,
                      stats=True)
        with jax.named_scope("eva_local"):
            # the row's own window, oldest block first, out of its ring
            first = cursors // W * (W // bt)
            window = _own(local_table, first[:, None] + jnp.arange(W // bt))
            local = paged_decode_attention(
                qb, keys, vals, window, jnp.where(live, jnp.mod(cursors, W) + 1, 0), **kernel)
        with jax.named_scope("eva_remote"):
            # what is VISIBLE of the summaries: the windows the cursor has left
            remote = paged_decode_attention(
                qb, arena["sk"], arena["sv"], summary_table,
                jnp.where(live, cursors // W * cfg.chunks_per_window, 0), **kernel)
        ctx = join_softmax(local, remote).astype(cfg.dtype)          # [S, H, dim]
    out = jnp.dot(ctx.reshape(S, -1), layer["wo"])
    with jax.named_scope("eva_summarise"):
        # the chunk this token completes (every row computes; the others'
        # summaries go to trash), out of the local arena as just written
        pos = jnp.maximum(cursors[:, None] - (C - 1) + jnp.arange(C), 0)     # [S, C]
        at = _own(local_table, pos // bt), jnp.mod(pos, bt)
        sk, sv = summarise(cfg, layer, keys[at].reshape(S, C, H, -1),
                           vals[at].reshape(S, C, H, -1))
        row = cursors // C
        block, width = row // bt, summary_table.shape[1]
        done = live & (jnp.mod(cursors + 1, C) == 0) & (block < width)
        sid = jnp.where(done, jnp.take_along_axis(
            summary_table, jnp.minimum(block, width - 1)[:, None], axis=1)[:, 0], summary_trash)
        skeys = arena["sk"].at[sid, jnp.mod(row, bt)].set(sk)
        svals = arena["sv"].at[sid, jnp.mod(row, bt)].set(sv)
    return out, {"k": keys, "v": vals, "sk": skeys, "sv": svals}


def decode_step(cfg: EvaConfig, params, cache, tok: jax.Array, summary_table: jax.Array,
                local_table: jax.Array, live: jax.Array, summary_trash: int):
    """One token for every slot. ``summary_table`` [S, view] (the block
    table's first columns), ``local_table`` [S, cols] (the rings, a dead
    row's all trash), ``live`` [S]. Returns (logits [S, vocab] float32,
    cache, int32 [3]: summaries written, windows completed, 0)."""
    cursors = cache["cursors"]
    x = params["embedding"][tok].astype(jnp.float32)
    out_cache = {"cursors": cursors + 1}
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _decode_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            cursors, summary_table, local_table, live, summary_trash)
        x = x + a
        x = x + _mlp(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps))
    ends = lambda n: jnp.sum(live & (jnp.mod(cursors + 1, n) == 0), dtype=jnp.int32)
    stats = jnp.stack([ends(cfg.chunk_size), ends(cfg.window), jnp.int32(0)])
    return _head(cfg, params, x), out_cache, stats


# -- prefill: one chunk of one prompt -------------------------------------------

def _chunk_attention(cfg: EvaConfig, layer, arena, h, start, n_valid, read_summary,
                     read_local, write_local, summary_trash: int):
    """h [P, d] at positions ``start + i``, all of one window. The queries
    read what earlier chunks left of this window in the ring ``read_local``,
    the chunk itself (causally) and the summaries of every earlier window
    through ``read_summary``; the chunk's keys and values go into the
    blocks ``write_local`` keeps (trash: nobody can read them later), and
    the summaries of its whole chunks into the summary arena."""
    P, H, hd = h.shape[0], cfg.n_heads, cfg.head_dim
    W, C, bt = cfg.window, cfg.chunk_size, arena["k"].shape[1]
    positions = start + jnp.arange(P)
    q, k, v = _qkv(cfg, layer, h, positions)
    with jax.named_scope("kv_write"):
        keys = arena["k"].at[write_local].set(k.reshape(P // bt, bt, -1))
        vals = arena["v"].at[write_local].set(v.reshape(P // bt, bt, -1))
    with jax.named_scope("attn_eva"):
        view = read_summary.shape[0]
        seen = jnp.arange(view * bt) < start // W * cfg.chunks_per_window
        # a summary is earlier than every query of the chunk: any position
        # no query lies before does; what is not visible yet is nowhere
        parts_k = [arena["sk"][read_summary].reshape(view * bt, H, hd), k]
        parts_v = [arena["sv"][read_summary].reshape(view * bt, H, hd), v]
        parts_pos = [jnp.where(seen, 0, NOWHERE), positions]
        if P < W:
            # the window's earlier chunks, as they lie in the ring
            first = start // W * (W // bt)
            old = read_local[jnp.mod(first + jnp.arange(W // bt), read_local.shape[0])]
            old_pos = start // W * W + jnp.arange(W)
            parts_k.insert(1, arena["k"][old].reshape(W, H, hd))
            parts_v.insert(1, arena["v"][old].reshape(W, H, hd))
            parts_pos.insert(1, jnp.where(old_pos < start, old_pos, NOWHERE))
        # the kernel takes heads first; one query head a KV head
        ctx = chunk_attention(
            jnp.swapaxes(q, 0, 1), jnp.swapaxes(jnp.concatenate(parts_k), 0, 1),
            jnp.swapaxes(jnp.concatenate(parts_v), 0, 1), positions,
            jnp.concatenate(parts_pos), scale=hd ** -0.5)
        ctx = jnp.swapaxes(ctx, 0, 1).reshape(P, H * hd)
    out = jnp.dot(ctx, layer["wo"])
    with jax.named_scope("eva_summarise"):
        sk, sv = summarise(cfg, layer, k.reshape(P // C, C, H, hd), v.reshape(P // C, C, H, hd))
        row = start // C + jnp.arange(P // C)
        block = row // bt
        whole = (row < (start + n_valid) // C) & (block < view)
        sid = jnp.where(whole, read_summary[jnp.minimum(block, view - 1)], summary_trash)
        skeys = arena["sk"].at[sid, jnp.mod(row, bt)].set(sk)
        svals = arena["sv"].at[sid, jnp.mod(row, bt)].set(sv)
    return out, {"k": keys, "v": vals, "sk": skeys, "sv": svals}


def prefill_chunk(cfg: EvaConfig, params, cache, ids: jax.Array, start, n_valid,
                  read_summary, read_local, write_local, summary_trash: int):
    """One chunk of one prompt: ``ids`` [P] at positions ``start ..`` (P
    divides the window and ``start`` is a multiple of P, so a chunk lies in
    one window), of which the first ``n_valid`` are real. ``read_summary``
    [view] is the row's block table of the summary kind, ``read_local``
    [cols] its ring as the previous chunk left it, ``write_local`` [P /
    block_t] the arena block each block of the chunk goes to (trash: not
    kept). Returns (logits of position ``n_valid - 1`` [vocab] float32,
    cache, int32 [3]: summaries written, windows completed, 0)."""
    P = ids.shape[0]
    if cfg.window % P or P % cfg.chunk_size:
        raise ValueError(f"a prefill chunk of {P} must divide the window "
                         f"({cfg.window}) and hold whole chunks of {cfg.chunk_size}")
    x = params["embedding"][ids].astype(jnp.float32)
    out_cache = {"cursors": cache["cursors"]}
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _chunk_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            start, n_valid, read_summary, read_local, write_local, summary_trash)
        x = x + a
        x = x + _mlp(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps))
    last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=0, keepdims=False)
    end = start + n_valid
    stats = jnp.stack([end // cfg.chunk_size - start // cfg.chunk_size,
                       end // cfg.window - start // cfg.window,
                       jnp.zeros_like(end)]).astype(jnp.int32)
    return _head(cfg, params, last), out_cache, stats
