"""MiMo-V2-style decoder for the serving path: window and full attention
mixed, grouped-query heads whose key and value widths differ, and sparse
experts of which this chip holds a share.

Plain functions over a parameter tree (no flax module: the serving engine
needs exactly two device programs of it, and both write straight into the
paged arenas):

- :func:`prefill_chunk` — one fixed-size chunk of ONE prompt, written into
  the blocks the engine granted it, attending to what earlier chunks left
  in the arenas. One program a chunk shape, whatever the prompt's length.
- :func:`decode_step` — one token for every slot, each at its own cursor.

Layer equations (``benchmark/reference/mimo.py`` is the plain float32
reading of the same): RMSNorm; attention of kind full (``kv_heads_full``
KV heads, ``rope_theta_full``) or window (``kv_heads_window``,
``rope_theta_window``, keys ``0 <= t - j < window``, a learnable per-head
sink in the softmax's denominator); rotary (half-split) on the first
``rotary_dim`` of the ``qk_dim`` dims; the attention output scaled by
``value_scale``; a dense SwiGLU or the held share of a sigmoid-routed
expert layer (``parallel/moe.py``); untied head. Parameters and matmul
operands are bfloat16; the residual stream, the router (its input too),
the norms' statistics, the softmax and the logits are float32.

Two kinds of cache side by side (``serving/paged.py``): a full layer keeps
every block of a row, read through the first columns of the row's block
table; a window layer keeps a ring of ``cols`` blocks a slot, logical block
``b`` in column ``b % cols``, and the engine gives a block back once the
cursor has left it behind by a window.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.chunk_attention import NOWHERE, chunk_attention
from ..ops.paged_attention import paged_decode_attention
from ..parallel.moe import held_experts_ffn, sigmoid_top_k

FULL, WINDOW = 0, 1


@dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 19072
    d_model: int = 4096
    n_heads: int = 64
    qk_dim: int = 192
    v_dim: int = 128
    kv_heads_full: int = 4
    kv_heads_window: int = 8
    window: int = 128
    rotary_dim: int = 64
    rope_theta_full: float = 1e7
    rope_theta_window: float = 1e4
    value_scale: float = 0.707
    #: ``hybrid_layer_pattern``: 0 = full attention, 1 = sliding window
    layer_kinds: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 0)
    #: ``moe_layer_freq``: 0 = dense SwiGLU, 1 = expert layer
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 1)
    d_ff_dense: int = 16384
    d_ff_expert: int = 2048
    n_experts: int = 256            # the router's outputs, all of them
    experts_per_token: int = 8
    held_experts: int = 16          # how many of them live on this chip
    first_held_expert: int = 0
    norm_eps: float = 1e-5
    max_seq: int = 8192
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def kv_heads(self, kind: int) -> int:
        return self.kv_heads_window if kind == WINDOW else self.kv_heads_full

    @classmethod
    def tiny(cls) -> "MimoConfig":
        return cls(vocab_size=256, d_model=64, n_heads=8, qk_dim=24, v_dim=16,
                   kv_heads_full=2, kv_heads_window=4, window=8, rotary_dim=8,
                   layer_kinds=(0, 1, 1, 0), moe_layers=(0, 1, 1, 1),
                   d_ff_dense=128, d_ff_expert=32, n_experts=16,
                   experts_per_token=4, held_experts=16, max_seq=128)


def init_params(cfg: MimoConfig, key: jax.Array) -> Dict[str, Any]:
    """Random weights in the tree the programs read: matrices N(0, 0.02),
    norms 1, sinks N(ln(3/7 window), 1) (a fifth to a half of a window's mass
    under such projections; N(4, 1) at window 128), router bias N(0, 0.01)."""
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 4))

    def mat(*shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dtype)

    layers = []
    for kind, moe in zip(cfg.layer_kinds, cfg.moe_layers):
        kv = cfg.kv_heads(kind)
        layer: Dict[str, Any] = {
            "norm_attn": jnp.ones((d,), dt), "norm_ffn": jnp.ones((d,), dt),
            "wq": mat(d, cfg.n_heads, cfg.qk_dim), "wk": mat(d, kv, cfg.qk_dim),
            "wv": mat(d, kv, cfg.v_dim), "wo": mat(cfg.n_heads, cfg.v_dim, d),
        }
        if kind == WINDOW:
            layer["sink"] = (math.log(3.0 / 7.0 * cfg.window)
                             + jax.random.normal(next(keys), (cfg.n_heads,), jnp.float32))
        if moe:
            f, n = cfg.d_ff_expert, cfg.held_experts
            layer["moe"] = {
                "router": mat(d, cfg.n_experts, dtype=jnp.float32),
                "router_bias": jax.random.normal(
                    next(keys), (cfg.n_experts,), jnp.float32) * 0.01,
                "w_gate": mat(n, d, f), "w_up": mat(n, d, f), "w_down": mat(n, f, d),
            }
        else:
            f = cfg.d_ff_dense
            layer["mlp"] = {"w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d)}
        layers.append(layer)
    return {"embedding": mat(cfg.vocab_size, d), "head": mat(d, cfg.vocab_size),
            "norm_final": jnp.ones((d,), dt), "layers": layers}


def fresh_cache(cfg: MimoConfig, slots: int, blocks: Dict[int, int], block_t: int
                ) -> Dict[str, Any]:
    """Arenas of both kinds (``blocks[kind]`` allocatable blocks plus the
    trash block) and one cursor a slot, shared by every layer. An arena is
    ``[blocks + 1, block_t, kv_heads * width]``: a position's KV heads lie
    side by side in ONE row (768 wide for 4 heads of 192: whole lanes, no
    padding of a 192-wide head), which is how a token is written, how the
    decode kernel fetches a page and how a gathered view is read; the heads
    are told apart in the matmul (:func:`_heads_apart`), not by moving
    what was read."""
    cache: Dict[str, Any] = {"cursors": jnp.zeros((slots,), jnp.int32)}
    for i, kind in enumerate(cfg.layer_kinds):
        rows, kv = (blocks[kind] + 1, block_t), cfg.kv_heads(kind)
        cache[f"layer_{i}"] = {"k": jnp.zeros(rows + (kv * cfg.qk_dim,), cfg.dtype),
                               "v": jnp.zeros(rows + (kv * cfg.v_dim,), cfg.dtype)}
    return cache


# -- pieces ---------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    """Float32 in, float32 out (the caller rounds what a matmul takes)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32)


def partial_rope(x: jax.Array, positions: jax.Array, theta: float, rotary_dim: int
                 ) -> jax.Array:
    """Rotary (half-split) on the first ``rotary_dim`` dims of the last
    axis; the rest pass through. x: [..., heads, dim], positions: [...]."""
    half = rotary_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None, None] * freqs     # [..., 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dim], xf[..., rotary_dim:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


def _qkv(cfg: MimoConfig, layer: Dict[str, Any], kind: int, h: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    theta = cfg.rope_theta_window if kind == WINDOW else cfg.rope_theta_full
    h = h.astype(cfg.dtype)
    q = jnp.einsum("td,dhk->thk", h, layer["wq"])
    k = jnp.einsum("td,dhk->thk", h, layer["wk"])
    v = jnp.einsum("td,dhk->thk", h, layer["wv"])
    return (partial_rope(q, positions, theta, cfg.rotary_dim),
            partial_rope(k, positions, theta, cfg.rotary_dim), v)


def _softmax(scores: jax.Array, mask: jax.Array, sink: jax.Array) -> jax.Array:
    """Float32 softmax over the last axis of the masked scores; the
    exponential of ``sink`` (broadcastable to the scores without their last
    axis) joins the denominator and takes no value."""
    scores = jnp.where(mask, scores, -1e30)
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink[..., None])
    e = jnp.where(mask, jnp.exp(scores - top), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink[..., None] - top)
    return e / jnp.maximum(den, 1e-30)


def _ffn(cfg: MimoConfig, layer: Dict[str, Any], h: jax.Array, live: jax.Array
         ) -> Tuple[jax.Array, jax.Array]:
    """h: the normalised residual stream, float32. Returns (the layer's
    output in the compute type, expert stats)."""
    low = h.astype(cfg.dtype)
    if "mlp" in layer:
        with jax.named_scope("mlp"):
            p = layer["mlp"]
            mid = jax.nn.silu(jnp.dot(low, p["w_gate"])) * jnp.dot(low, p["w_up"])
            return jnp.dot(mid, p["w_down"]), jnp.zeros((3,), jnp.int32)
    p = layer["moe"]
    with jax.named_scope("moe_router"):
        idx, w = sigmoid_top_k(h, p["router"], p["router_bias"], cfg.experts_per_token)
    return held_experts_ffn(low, idx, w, p["w_gate"], p["w_up"], p["w_down"],
                            first_held=cfg.first_held_expert, live=live)


def ring_positions(newest_block: jax.Array, cols: int, block_t: int) -> jax.Array:
    """The position each place of a window ring holds, ``[..., cols *
    block_t]``: column ``j`` holds the newest logical block ``b <=
    newest_block`` with ``b % cols == j`` (negative: never written)."""
    nb = newest_block[..., None]
    b = nb - jnp.mod(nb - jnp.arange(cols), cols)                 # [..., cols]
    pos = b[..., None] * block_t + jnp.arange(block_t)
    return pos.reshape(pos.shape[:-2] + (cols * block_t,))


# -- decode: one token for every slot ---------------------------------------------

def _heads_apart(x: jax.Array, kv: int) -> jax.Array:
    """x [..., kv, g, dim] -> [..., kv * g, kv * dim] with head ``(k, j)``'s
    vector in column block ``k`` and zeros elsewhere: a product with a row
    of ``kv`` heads side by side then reads only the query's own KV head.
    (It costs ``kv`` times the multiply-adds, which a decode step does not
    notice; moving a gathered view so that heads lead costs its bytes.)"""
    eye = jnp.eye(kv, dtype=x.dtype)
    out = x[..., :, :, None, :] * eye[:, None, :, None]           # [..., kv, g, kv, dim]
    return out.reshape(x.shape[:-3] + (kv * x.shape[-2], kv * x.shape[-1]))


def _own_head(x: jax.Array, kv: int) -> jax.Array:
    """x [..., kv * g, kv * dim] -> [..., kv * g, dim]: each query head's
    own KV head's block of columns."""
    g, dim = x.shape[-2] // kv, x.shape[-1] // kv
    x = x.reshape(x.shape[:-2] + (kv, g, kv, dim))
    return jnp.einsum("...kgjd,kj->...kgd", x, jnp.eye(kv, dtype=x.dtype)).reshape(
        x.shape[:-4] + (kv * g, dim))


def _decode_attention(cfg: MimoConfig, layer, kind: int, arena, h, cursors,
                      table, live, trash: int):
    """h [S, d] at positions ``cursors`` [S]. Writes this token's key and
    value through ``table`` and attends over what the row holds: a window
    layer over its ring's view, a full layer over its own pages
    (``ops.paged_attention``: a row that is not ``live`` reads nothing and
    gets zeros)."""
    S = h.shape[0]
    kv = cfg.kv_heads(kind)
    bt = arena["k"].shape[1]
    width = table.shape[1]
    q, k, v = _qkv(cfg, layer, kind, h, cursors)
    block = cursors // bt
    with jax.named_scope("kv_write"):
        if kind == WINDOW:
            col = jnp.mod(block, width)
            ids = jnp.take_along_axis(table, col[:, None], axis=1)[:, 0]
        else:
            # a row past the columns it was handed (dead, or past its
            # budget) writes to trash
            ids = jnp.where(block < width, jnp.take_along_axis(
                table, jnp.minimum(block, width - 1)[:, None], axis=1)[:, 0], trash)
        off = jnp.mod(cursors, bt)
        keys_arena = arena["k"].at[ids, off].set(k.reshape(S, -1))
        vals_arena = arena["v"].at[ids, off].set(v.reshape(S, -1))
    with jax.named_scope("attn_window" if kind == WINDOW else "attn_full"):
        qb = _heads_apart(q.reshape(S, kv, cfg.n_heads // kv, cfg.qk_dim), kv)
        if kind == WINDOW:
            keys = keys_arena[table].reshape(S, width * bt, kv * cfg.qk_dim)
            vals = vals_arena[table].reshape(S, width * bt, kv * cfg.v_dim)
            pos = ring_positions(block, width, bt)                # [S, T]
            mask = ((pos >= 0) & (pos <= cursors[:, None])
                    & (cursors[:, None] - pos < cfg.window))
            scores = jnp.einsum("shc,stc->sht", qb, keys,
                                preferred_element_type=jnp.float32) * cfg.qk_dim ** -0.5
            probs = _softmax(scores, mask[:, None, :], layer["sink"][None, :])
            ctx = _own_head(jnp.einsum("sht,stc->shc", probs.astype(cfg.dtype), vals,
                                       preferred_element_type=jnp.float32), kv)
        else:
            ctx = paged_decode_attention(
                qb, keys_arena, vals_arena, table, jnp.where(live, cursors + 1, 0),
                scale=cfg.qk_dim ** -0.5, kv_heads=kv)
        ctx = (ctx * cfg.value_scale).astype(cfg.dtype)             # [S, heads, v]
    out = jnp.einsum("shd,hdm->sm", ctx, layer["wo"])
    return out, {"k": keys_arena, "v": vals_arena}


def decode_step(cfg: MimoConfig, params, cache, tok: jax.Array,
                full_table: jax.Array, window_table: jax.Array, live: jax.Array,
                trash: Dict[int, int]):
    """One token for every slot. ``full_table`` [S, view] (the block
    table's first columns), ``window_table`` [S, cols] (the rings),
    ``live`` [S] (rows that belong to a request: the others take no expert).
    Returns (logits [S, vocab] float32, cache, expert stats int32 [3])."""
    cursors = cache["cursors"]
    x = params["embedding"][tok].astype(jnp.float32)
    out_cache = {"cursors": cursors + 1}
    stats = jnp.zeros((3,), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        table = window_table if kind == WINDOW else full_table
        a, out_cache[f"layer_{i}"] = _decode_attention(
            cfg, layer, kind, cache[f"layer_{i}"],
            rms_norm(x, layer["norm_attn"], cfg.norm_eps), cursors, table, live,
            trash[kind])
        x = x + a
        f, st = _ffn(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), live)
        x = x + f
        stats = stats + st
    with jax.named_scope("lm_head"):
        last = rms_norm(x, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)
        logits = jnp.dot(last, params["head"], preferred_element_type=jnp.float32)
    return logits, out_cache, stats


# -- prefill: one chunk of one prompt -------------------------------------------

def _chunk_attention(cfg: MimoConfig, layer, kind: int, arena, h, start, read, write):
    """h [C, d] at positions ``start + i``. Full kind: the chunk's keys and
    values go into the blocks ``write`` names, then the queries read the
    row's view ``read``. Window kind: the queries read what is left of the
    earlier chunks (the ring ``read``, as it was before this chunk) and the
    chunk itself, and only the blocks ``write`` keeps (the others are
    trash) go into the arena. Both through ``ops.chunk_attention``, which
    masks by position."""
    C = h.shape[0]
    kv = cfg.kv_heads(kind)
    g = cfg.n_heads // kv
    bt = arena["k"].shape[1]
    positions = start + jnp.arange(C)
    q, k, v = _qkv(cfg, layer, kind, h, positions)
    with jax.named_scope("kv_write"):
        keys_arena = arena["k"].at[write].set(k.reshape(C // bt, bt, -1))
        vals_arena = arena["v"].at[write].set(v.reshape(C // bt, bt, -1))
    with jax.named_scope("attn_window" if kind == WINDOW else "attn_full"):
        if kind == WINDOW:
            cols = read.shape[0]
            old_pos = ring_positions((start - 1) // bt, cols, bt)
            old_pos = jnp.where((old_pos >= 0) & (old_pos < start), old_pos, NOWHERE)
            keys = jnp.concatenate([arena["k"][read].reshape(cols * bt, kv, cfg.qk_dim), k])
            vals = jnp.concatenate([arena["v"][read].reshape(cols * bt, kv, cfg.v_dim), v])
            key_pos = jnp.concatenate([old_pos, positions])
            sink = jnp.tile(layer["sink"].reshape(kv, 1, g), (1, C, 1)).reshape(kv, C * g)
        else:
            view = read.shape[0]
            keys = keys_arena[read].reshape(view * bt, kv, cfg.qk_dim)
            vals = vals_arena[read].reshape(view * bt, kv, cfg.v_dim)
            key_pos = jnp.arange(view * bt)
            sink = None
        # the kernel takes KV heads first (ONE row's view: a few MB to move),
        # and a group's query heads as consecutive rows of one position
        rows = jnp.swapaxes(q.reshape(C, kv, g, cfg.qk_dim), 0, 1).reshape(kv, C * g, cfg.qk_dim)
        ctx = chunk_attention(
            rows, jnp.swapaxes(keys, 0, 1), jnp.swapaxes(vals, 0, 1),
            jnp.repeat(positions, g), key_pos, scale=cfg.qk_dim ** -0.5,
            window=cfg.window if kind == WINDOW else None, sink=sink)
        ctx = jnp.swapaxes(ctx.reshape(kv, C, g, cfg.v_dim), 0, 1)
        ctx = (ctx.astype(jnp.float32) * cfg.value_scale).astype(cfg.dtype)
    out = jnp.einsum("thd,hdm->tm", ctx.reshape(C, cfg.n_heads, cfg.v_dim), layer["wo"])
    return out, {"k": keys_arena, "v": vals_arena}


def prefill_chunk(cfg: MimoConfig, params, cache, ids: jax.Array, start, n_valid,
                  read_full, write_full, read_window, write_window):
    """One chunk of one prompt: ``ids`` [C] at positions ``start ..``, of
    which the first ``n_valid`` are real. ``read_full`` [view] is the
    row's block table (its first columns, this chunk's blocks included),
    ``read_window`` [cols] its ring as the previous chunk left it;
    ``write_full`` / ``write_window`` [C / block_t] name the arena block
    each block of the chunk goes to (trash: not kept). The cursors are the
    engine's to set. Returns (logits of position ``n_valid - 1`` [vocab]
    float32, cache, expert stats int32 [3])."""
    x = params["embedding"][ids].astype(jnp.float32)
    live = jnp.arange(ids.shape[0]) < n_valid
    out_cache = {"cursors": cache["cursors"]}
    stats = jnp.zeros((3,), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        read, write = ((read_window, write_window) if kind == WINDOW
                       else (read_full, write_full))
        a, out_cache[f"layer_{i}"] = _chunk_attention(
            cfg, layer, kind, cache[f"layer_{i}"],
            rms_norm(x, layer["norm_attn"], cfg.norm_eps), start, read, write)
        x = x + a
        f, st = _ffn(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), live)
        x = x + f
        stats = stats + st
    with jax.named_scope("lm_head"):
        last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=0, keepdims=False)
        last = rms_norm(last, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)
        logits = jnp.dot(last, params["head"], preferred_element_type=jnp.float32)
    return logits, out_cache, stats
