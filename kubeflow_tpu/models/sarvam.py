"""Sarvam-style decoder for the serving path: latent attention (MLA, as
DeepSeek-V2/V3 without a query low rank) with YaRN rotary, a leading dense
layer, then expert layers of a shared expert beside sigmoid-routed experts
of which this chip holds a share.

Plain functions over a parameter tree, as ``models/mimo.py``: the serving
engine needs two device programs of it, and both write straight into the
paged latent arena:

- :func:`prefill_chunk` — one fixed-size chunk of ONE prompt, its rows
  written into the blocks the engine granted it, its queries attending in
  the EXPANDED form to the row's whole view (``ops/latent_attention``:
  the latent expanded per key block, never the whole view at once).
- :func:`decode_step` — one token for every slot in the ABSORBED form: each
  head's query folded into latent space, scored against the 640-wide rows
  of the slot's own pages, the context mapped out of latent space after.

Layer equations (``benchmark/reference/sarvam.py`` is the plain float32
reading of the same), per token with ``h = RMSNorm(x)``:
  ``q = h W_q`` (64 heads of 192), RMSNorm over each head's 192 dims (one
  gain shared by the heads: the reading of ``use_qk_norm``), then ``q =
  [q_nope (128) | RoPE(q_rope) (64)]``;
  ``[c | k_r] = h W_dkv`` (512 + 64), ``c <- RMSNorm(c)``, ``k_r <-
  RoPE(k_r)``, one rotary key shared by the heads;
  head ``i``: key ``[c W_uk_i | k_r]``, value ``c W_uv_i``; causal softmax
  with scale ``192^-0.5 * mscale^2``; output ``W_o`` over the heads' 128.
  Rotary is YaRN (``deepseek_yarn``): inverse frequencies ramp between the
  original and the ``1 / factor`` ones over the dims between the correction
  bounds of ``beta_fast`` and ``beta_slow``; half-split, as the repo's
  rotary. Layer 0 a dense SwiGLU; the others ``shared(h) + routed_scaling x``
  the held share of the top ``k`` of ``sigmoid(h W_r)`` chosen on ``+ b``
  (``parallel/moe.shared_expert_layer``). Final RMSNorm, untied head over the
  vocabulary slice. Parameters and matmul operands are bfloat16; the
  residual stream, the router (its input too), the norms' statistics, the
  softmaxes and the logits are float32.

One kind of cache (``serving/paged.py``'s block table alone): one arena a
layer ``[blocks + 1, block_t, 640]``, a row a position holding
``[RMSNorm(c) | RoPE(k_r) | 0]`` at the position's own angle.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.latent_attention import (latent_decode_attention, latent_prefill_attention,
                                    row_width)
from ..parallel.moe import shared_expert_layer, sigmoid_top_k
from .mimo import rms_norm

#: counters the step and a prefill chunk return beside their results:
#: assignments on held experts, the busiest held expert's, held experts
#: touched (``parallel/moe.held_experts_ffn``), summed over the layers; and
#: the latent pages the decode attention fetched, a layer (every layer
#: fetches as many)
STATS = 4


@dataclass(frozen=True)
class SarvamConfig:
    vocab_size: int = 65536
    d_model: int = 4096
    n_heads: int = 64
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    kv_lora_rank: int = 512
    v_dim: int = 128
    #: 0 = dense SwiGLU (``first_k_dense_replace``), 1 = expert layer
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1)
    d_ff_dense: int = 16384
    d_ff_expert: int = 2048
    d_ff_shared: int = 2048
    n_experts: int = 128            # the router's outputs, all of them
    experts_per_token: int = 8
    held_experts: int = 32          # how many of them live on this chip
    first_held_expert: int = 0
    routed_scaling: float = 2.5
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq: int = 32768
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.moe_layers)

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def row(self) -> int:
        """An arena row: latent and rotary key, padded to whole lanes."""
        return row_width(self.kv_lora_rank, self.qk_rope_dim)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim)
        return self.qk_dim ** -0.5 * m * m

    @classmethod
    def tiny(cls) -> "SarvamConfig":
        return cls(vocab_size=256, d_model=64, n_heads=4, qk_nope_dim=16, qk_rope_dim=8,
                   kv_lora_rank=32, v_dim=16, moe_layers=(0, 1, 1), d_ff_dense=128,
                   d_ff_expert=32, d_ff_shared=32, n_experts=16, experts_per_token=4,
                   held_experts=8, rope_factor=4.0, rope_original_max=32, max_seq=128)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: SarvamConfig) -> jax.Array:
    """YaRN's inverse frequencies over the rotary dims' pairs: the original
    ``theta^(-2i / dim)`` below the ``beta_fast`` bound, ``1 / factor`` of
    them above the ``beta_slow`` bound, a linear ramp between."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta

    def bound(rotations: float) -> float:
        return (dim * math.log(cfg.rope_original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(bound(cfg.beta_fast)), 0)
    high = min(math.ceil(bound(cfg.beta_slow)), dim - 1)
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def rope(cfg: SarvamConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """YaRN rotary (half-split) over the last axis of float32 ``x [...,
    (heads,) rope]``; ``positions`` [T] for ``x [T, ...]``."""
    half = cfg.qk_rope_dim // 2
    scale = yarn_mscale(cfg.rope_factor, cfg.mscale) / yarn_mscale(cfg.rope_factor,
                                                                   cfg.mscale_all_dim)
    ang = positions.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) \
        * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def init_params(cfg: SarvamConfig, key: jax.Array) -> Dict[str, Any]:
    """Random weights in the tree the programs read: matrices N(0, 0.02),
    norms 1, router bias N(0, 0.01)."""
    d, dt, H = cfg.d_model, cfg.dtype, cfg.n_heads
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 4))

    def mat(*shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02).astype(dtype)

    def swiglu(f, n=()):
        return {"w_gate": mat(*n, d, f), "w_up": mat(*n, d, f), "w_down": mat(*n, f, d)}

    layers = []
    for moe in cfg.moe_layers:
        layer: Dict[str, Any] = {
            "norm_attn": jnp.ones((d,), dt), "norm_ffn": jnp.ones((d,), dt),
            "wq": mat(d, H, cfg.qk_dim), "q_norm": jnp.ones((cfg.qk_dim,), dt),
            "w_dkv": mat(d, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
            "w_uk": mat(H, cfg.kv_lora_rank, cfg.qk_nope_dim),
            "w_uv": mat(H, cfg.kv_lora_rank, cfg.v_dim), "wo": mat(H, cfg.v_dim, d),
        }
        if moe:
            layer["moe"] = {
                "router": mat(d, cfg.n_experts, dtype=jnp.float32),
                "router_bias": jax.random.normal(
                    next(keys), (cfg.n_experts,), jnp.float32) * 0.01,
                **swiglu(cfg.d_ff_expert, (cfg.held_experts,)),
                "shared": swiglu(cfg.d_ff_shared)}
        else:
            layer["mlp"] = swiglu(cfg.d_ff_dense)
        layers.append(layer)
    return {"embedding": mat(cfg.vocab_size, d), "head": mat(d, cfg.vocab_size),
            "norm_final": jnp.ones((d,), dt), "layers": layers}


def fresh_cache(cfg: SarvamConfig, slots: int, blocks: int, block_t: int) -> Dict[str, Any]:
    """One latent arena a layer (``blocks`` allocatable blocks plus the
    trash block), ``[blocks + 1, block_t, row]``, and one cursor a slot."""
    cache: Dict[str, Any] = {"cursors": jnp.zeros((slots,), jnp.int32)}
    for i in range(cfg.n_layers):
        cache[f"layer_{i}"] = jnp.zeros((blocks + 1, block_t, cfg.row), cfg.dtype)
    return cache


# -- pieces ---------------------------------------------------------------------

def _queries(cfg: SarvamConfig, layer, h: jax.Array, positions: jax.Array):
    """h [T, d] float32. Returns (q_nope [T, H, nope], q_rope [T, H, rope]),
    float32, normalised and rotated."""
    with jax.named_scope("mla_q"):
        q = jnp.einsum("td,dhk->thk", h.astype(cfg.dtype), layer["wq"],
                       preferred_element_type=jnp.float32)
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        return q[..., :cfg.qk_nope_dim], rope(cfg, q[..., cfg.qk_nope_dim:], positions)


def _latent_rows(cfg: SarvamConfig, layer, h: jax.Array, positions: jax.Array) -> jax.Array:
    """The arena's rows of ``h`` [T, d]: ``[RMSNorm(c) | RoPE(k_r) | 0]``,
    [T, row] in the compute type."""
    r = cfg.kv_lora_rank
    ckr = jnp.dot(h.astype(cfg.dtype), layer["w_dkv"], preferred_element_type=jnp.float32)
    c = rms_norm(ckr[:, :r], layer["kv_norm"], cfg.norm_eps)
    kr = rope(cfg, ckr[:, r:], positions)
    pad = jnp.zeros((h.shape[0], cfg.row - r - cfg.qk_rope_dim), jnp.float32)
    return jnp.concatenate([c, kr, pad], axis=-1).astype(cfg.dtype)


def _ffn(cfg: SarvamConfig, layer, h: jax.Array, live: jax.Array):
    """h: the normalised residual stream, float32. Returns (the layer's
    output, float32, expert stats int32 [3])."""
    low = h.astype(cfg.dtype)
    if "mlp" in layer:
        with jax.named_scope("mlp"):
            p = layer["mlp"]
            mid = jax.nn.silu(jnp.dot(low, p["w_gate"])) * jnp.dot(low, p["w_up"])
            return (jnp.dot(mid, p["w_down"], preferred_element_type=jnp.float32),
                    jnp.zeros((3,), jnp.int32))
    p = layer["moe"]
    with jax.named_scope("moe_router"):
        idx, w = sigmoid_top_k(h, p["router"], p["router_bias"], cfg.experts_per_token)
    return shared_expert_layer(low, idx, w, p, routed_scale=cfg.routed_scaling,
                               first_held=cfg.first_held_expert, live=live)


def _head(cfg: SarvamConfig, params, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        last = rms_norm(x, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(last, params["head"], preferred_element_type=jnp.float32)


# -- decode: one token for every slot, absorbed -------------------------------------

def _decode_attention(cfg: SarvamConfig, layer, arena, h, cursors, table, live, trash: int):
    """h [S, d] at positions ``cursors`` [S]. Writes this token's row
    through ``table`` and attends, absorbed, over the row's own pages (a
    row that is not ``live`` reads nothing and gets zeros)."""
    bt, width = arena.shape[1], table.shape[1]
    q_nope, q_rope = _queries(cfg, layer, h, cursors)
    with jax.named_scope("mla_latent"):
        rows = _latent_rows(cfg, layer, h, cursors)
        block = cursors // bt
        # a row past the columns it was handed (dead, or past its budget)
        # writes to trash
        ids = jnp.where(block < width, jnp.take_along_axis(
            table, jnp.minimum(block, width - 1)[:, None], axis=1)[:, 0], trash)
        arena = arena.at[ids, jnp.mod(cursors, bt)].set(rows)
    with jax.named_scope("attn_mla_decode"):
        q_lat = jnp.einsum("shn,hcn->shc", q_nope.astype(cfg.dtype), layer["w_uk"],
                           preferred_element_type=jnp.float32)
        pad = jnp.zeros(q_lat.shape[:2] + (cfg.row - cfg.kv_lora_rank - cfg.qk_rope_dim,),
                        jnp.float32)
        q = jnp.concatenate([q_lat, q_rope, pad], axis=-1).astype(cfg.dtype)
        ctx = latent_decode_attention(q, arena, table, jnp.where(live, cursors + 1, 0),
                                      scale=cfg.softmax_scale, latent=cfg.kv_lora_rank)
        o = jnp.einsum("shc,hcv->shv", ctx.astype(cfg.dtype), layer["w_uv"],
                       preferred_element_type=jnp.float32)
    out = jnp.einsum("shv,hvm->sm", o.astype(cfg.dtype), layer["wo"],
                     preferred_element_type=jnp.float32)
    return out, arena


def decode_step(cfg: SarvamConfig, params, cache, tok: jax.Array, table: jax.Array,
                trash: int):
    """One token for every slot. ``table`` [S, view] (the block table's
    first columns; a row whose first column is trash belongs to no request:
    it reads nothing and takes no expert). Returns (logits [S, vocab]
    float32, cache, stats int32 [STATS])."""
    cursors = cache["cursors"]
    bt = cache["layer_0"].shape[1]
    live = table[:, 0] != trash
    x = params["embedding"][tok].astype(jnp.float32)
    out_cache = {"cursors": cursors + 1}
    moe = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _decode_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            cursors, table, live, trash)
        x = x + a
        f, st = _ffn(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), live)
        x = x + f
        moe = moe + st
    pages = jnp.sum(jnp.where(live, jnp.minimum(cursors // bt + 1, table.shape[1]), 0),
                    dtype=jnp.int32)
    return _head(cfg, params, x), out_cache, jnp.concatenate([moe, pages[None]])


# -- prefill: one chunk of one prompt, expanded ------------------------------------

def _chunk_attention(cfg: SarvamConfig, layer, arena, h, start, read, write):
    """h [C, d] at positions ``start + i``: the chunk's rows go into the
    blocks ``write`` names, then its queries attend, expanded, over the
    row's view ``read``."""
    C = h.shape[0]
    bt = arena.shape[1]
    positions = start + jnp.arange(C)
    q_nope, q_rope = _queries(cfg, layer, h, positions)
    with jax.named_scope("mla_latent"):
        rows = _latent_rows(cfg, layer, h, positions)
        arena = arena.at[write].set(rows.reshape(C // bt, bt, cfg.row))
    with jax.named_scope("attn_mla_prefill"):
        view = arena[read].reshape(read.shape[0] * bt, cfg.row)
        tail = cfg.row - cfg.kv_lora_rank - cfg.qk_rope_dim
        q = jnp.pad(jnp.concatenate([q_nope, q_rope], axis=-1), ((0, 0), (0, 0), (0, tail)))
        ctx = latent_prefill_attention(
            jnp.swapaxes(q, 0, 1).astype(cfg.dtype), view, layer["w_uk"], layer["w_uv"],
            start, scale=cfg.softmax_scale)                              # [H, C, v]
    out = jnp.einsum("hcv,hvm->cm", ctx, layer["wo"], preferred_element_type=jnp.float32)
    return out, arena


def prefill_chunk(cfg: SarvamConfig, params, cache, ids: jax.Array, start, n_valid,
                  read: jax.Array, write: jax.Array):
    """One chunk of one prompt: ``ids`` [C] at positions ``start ..``, of
    which the first ``n_valid`` are real. ``read`` [view] is the row's block
    table (its first columns, this chunk's blocks included); ``write`` [C /
    block_t] names the arena block each block of the chunk goes to (trash:
    not kept). The cursors are the engine's to set. Returns (logits of
    position ``n_valid - 1`` [vocab] float32, cache, stats int32
    [STATS])."""
    x = params["embedding"][ids].astype(jnp.float32)
    live = jnp.arange(ids.shape[0]) < n_valid
    out_cache = {"cursors": cache["cursors"]}
    moe = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        a, out_cache[f"layer_{i}"] = _chunk_attention(
            cfg, layer, cache[f"layer_{i}"], rms_norm(x, layer["norm_attn"], cfg.norm_eps),
            start, read, write)
        x = x + a
        f, st = _ffn(cfg, layer, rms_norm(x, layer["norm_ffn"], cfg.norm_eps), live)
        x = x + f
        moe = moe + st
    last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=0, keepdims=False)
    return _head(cfg, params, last), out_cache, jnp.concatenate([moe, jnp.zeros((1,), jnp.int32)])
