"""Nemotron-H-style hybrid decoder for the serving path: Mamba-2 state-space
mixers, latent expert layers of relu² experts and GQA attention, one kind
a layer, in the order of ``pattern`` (``M``, ``E``, ``*``).

Plain functions over a parameter tree, as ``models/mimo.py``: the serving
engine needs two device programs of it, both writing straight into the
caches:

- :func:`prefill_chunk` — one fixed-size chunk of ONE prompt: the
  attention layers' keys and values go into the blocks the engine granted,
  and each Mamba layer's state of the prompt's slot is zeroed at the
  prompt's first chunk (``start == 0``) and carried otherwise: the chunked
  scan (``ops/ssm.ssd_scan``) starts from it and the chunk's last state and
  last convolution inputs go back to the slot.
- :func:`decode_step` — one token for every slot: the attention layers
  through ``paged_decode_attention``, each Mamba layer's state of every
  LIVE row read and written once by ``ops/ssm.ssm_decode_update``, whose
  walk runs over the live rows alone; a row that is not live (free, or a
  slot still filling between its chunks) costs the update nothing and keeps
  its state and its convolution window as they were.

Layer equations (``benchmark/reference/nemotron.py`` is the plain float32
reading of the same), every layer ``x <- x + mixer(RMSNorm(x))``:

- ``M``: ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv1d_causal(xBC) +
  b)`` (depthwise, width ``conv_kernel``); ``x`` [H, P], ``B``, ``C`` [G, N]
  (head h reads group ``h // (H / G)``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``S_t = exp(dt A) S_{t-1} + dt x (outer) B``, ``y =
  S_t C + D x``; ``y = RMSNorm_groups(y * silu(z))`` over ``n_groups``
  groups of channels; ``out = y W_out``.
- ``*``: grouped-query causal attention, no rotary (the state-space layers
  order the sequence), scale ``head_dim^-0.5``.
- ``E``: ``s = sigmoid(h W_r)`` over all experts in float32, the top ``k``
  of ``s + bias``, weights ``s`` normalised over them;
  ``shared(h) + routed_scaling x latent_up(sum_held w_e relu(h_L W1_e)²
  W2_e)`` with ``h_L = h latent_down`` (``parallel/moe.shared_expert_layer``;
  no ``w_gate``: relu²); the shared expert ``relu(h Ws1)² Ws2`` at full width.

Final RMSNorm, untied head over the vocabulary slice. Matrices and matmul
operands are bfloat16; the residual stream, the router, the norms'
statistics, the softmax, the logits, the mixer's small parameters (the
convolution, ``dt_bias``, ``A_log``, ``D``) and every state are float32.

Caches: ``cursors`` [S]; an attention layer's arenas ``[blocks + 1, block_t,
kv_heads * head_dim]`` (keys and values, the block table's); a Mamba
layer's ``ssm`` [S, G, H / G, P, N] and ``conv`` [S, conv_kernel - 1,
channels], a fixed size a slot whatever its length (``serving/paged``'s
state kind: never paged).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.chunk_attention import chunk_attention
from ..ops.paged_attention import paged_decode_attention
from ..ops.ssm import ssd_scan, ssm_decode_update
from ..parallel.moe import shared_expert_layer, sigmoid_top_k
from .mimo import _heads_apart, rms_norm

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: counters the step and a prefill chunk return beside their results: the
#: held experts' three (``parallel/moe.held_experts_ffn``), the Mamba states
#: read and written (a live row's a step, the prompt's slot a chunk; summed
#: over the layers), whether a chunk zeroed its slot's states (a prompt's
#: first chunk) and the KV pages the decode attention fetched, a layer (0
#: for a chunk)
STATS = 6


@dataclass(frozen=True)
class NemotronConfig:
    vocab_size: int = 32768
    d_model: int = 4096
    #: one letter a layer: M Mamba-2, E latent expert layer, * attention
    pattern: str = "EMEMEMEMEM*"
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    ssm_chunk: int = 128
    n_heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 512            # the router's outputs, all of them
    held_experts: int = 128         # how many of them live on this chip
    first_held_expert: int = 0
    experts_per_token: int = 22
    moe_latent: int = 1024
    d_ff_expert: int = 2688
    d_ff_shared: int = 5376
    routed_scaling: float = 5.0
    norm_eps: float = 1e-5
    max_seq: int = 16384
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @classmethod
    def tiny(cls) -> "NemotronConfig":
        return cls(vocab_size=256, d_model=64, pattern="MEM*E", mamba_heads=8,
                   mamba_head_dim=8, ssm_state=16, n_groups=2, ssm_chunk=8, n_heads=4,
                   kv_heads=2, head_dim=16, n_experts=16, held_experts=8,
                   experts_per_token=4, moe_latent=16, d_ff_expert=32, d_ff_shared=48,
                   max_seq=128)


def init_params(cfg: NemotronConfig, key: jax.Array) -> Dict[str, Any]:
    """Random weights in the tree the programs read: matrices N(0, 0.02),
    norms 1, router bias N(0, 0.01), the convolution U(-1/2, 1/2), ``A_log``
    ``log U(1, 16)``, ``D`` 1, ``dt_bias`` for ``dt`` in [0.001, 0.1]."""
    d, dt, f32 = cfg.d_model, cfg.dtype, jnp.float32
    keys = iter(jax.random.split(key, 8 * cfg.n_layers + 4))

    def mat(*shape, dtype=dt, std=0.02):
        return (jax.random.normal(next(keys), shape, f32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, f32, lo, hi)

    layers = []
    for kind in cfg.pattern:
        if kind == MAMBA:
            H, ch = cfg.mamba_heads, cfg.conv_channels
            step = jnp.exp(uniform((H,), jnp.log(1e-3), jnp.log(1e-1)))
            mixer = {"w_in": mat(d, cfg.d_inner + ch + H), "w_out": mat(cfg.d_inner, d),
                     "conv_w": uniform((cfg.conv_kernel, ch), -0.5, 0.5),
                     "conv_b": uniform((ch,), -0.5, 0.5),
                     "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                     "A_log": jnp.log(uniform((H,), 1.0, 16.0)), "D": jnp.ones((H,), f32),
                     "norm": jnp.ones((cfg.d_inner,), dt)}
            layers.append({"norm": jnp.ones((d,), dt), "mamba": mixer})
        elif kind == ATTENTION:
            hd, kv = cfg.head_dim, cfg.kv_heads
            layers.append({"norm": jnp.ones((d,), dt), "attn": {
                "wq": mat(d, cfg.n_heads, hd), "wk": mat(d, kv, hd), "wv": mat(d, kv, hd),
                "wo": mat(cfg.n_heads, hd, d)}})
        else:
            n, L = cfg.held_experts, cfg.moe_latent
            layers.append({"norm": jnp.ones((d,), dt), "moe": {
                "router": mat(d, cfg.n_experts, dtype=f32),
                "router_bias": mat(cfg.n_experts, dtype=f32, std=0.01),
                "latent_down": mat(d, L), "latent_up": mat(L, d),
                "w_up": mat(n, L, cfg.d_ff_expert), "w_down": mat(n, cfg.d_ff_expert, L),
                "shared": {"w_up": mat(d, cfg.d_ff_shared), "w_down": mat(cfg.d_ff_shared, d)}}})
    return {"embedding": mat(cfg.vocab_size, d), "head": mat(d, cfg.vocab_size),
            "norm_final": jnp.ones((d,), dt), "layers": layers}


def fresh_cache(cfg: NemotronConfig, slots: int, blocks: int, block_t: int) -> Dict[str, Any]:
    """Every layer's cache at zero: an attention layer's two arenas
    (``blocks`` allocatable blocks plus the trash block), a Mamba layer's
    state and convolution window a slot; one cursor a slot."""
    cache: Dict[str, Any] = {"cursors": jnp.zeros((slots,), jnp.int32)}
    G = cfg.n_groups
    for i, kind in enumerate(cfg.pattern):
        if kind == ATTENTION:
            rows = (blocks + 1, block_t, cfg.kv_heads * cfg.head_dim)
            cache[f"layer_{i}"] = {"k": jnp.zeros(rows, cfg.dtype),
                                   "v": jnp.zeros(rows, cfg.dtype)}
        elif kind == MAMBA:
            cache[f"layer_{i}"] = {
                "ssm": jnp.zeros((slots, G, cfg.mamba_heads // G, cfg.mamba_head_dim,
                                  cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_channels),
                                  jnp.float32)}
    return cache


# -- pieces ---------------------------------------------------------------------

def _split(cfg: NemotronConfig, zxbcdt: jax.Array):
    """The input projection's columns: (z, xBC, dt)."""
    di, ch = cfg.d_inner, cfg.conv_channels
    return zxbcdt[..., :di], zxbcdt[..., di:di + ch], zxbcdt[..., di + ch:]


def _x_b_c(cfg: NemotronConfig, xbc: jax.Array):
    """The convolved channels as (x [..., H, P], B, C [..., G, N])."""
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (cfg.mamba_heads, cfg.mamba_head_dim)),
            xbc[..., di:di + gn].reshape(lead + (cfg.n_groups, cfg.ssm_state)),
            xbc[..., di + gn:].reshape(lead + (cfg.n_groups, cfg.ssm_state)))


def _gated_norm(cfg: NemotronConfig, p, y: jax.Array, z: jax.Array) -> jax.Array:
    """``RMSNorm(y * silu(z))`` over each of ``n_groups`` groups of the
    channels, one gain; float32 in and out."""
    with jax.named_scope("ssm_norm"):
        g = y * jax.nn.silu(z)
        lead = g.shape[:-1]
        g = g.reshape(lead + (cfg.n_groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
        return g.reshape(lead + (cfg.d_inner,)) * p["norm"].astype(jnp.float32)


def _project_in(cfg: NemotronConfig, p, h: jax.Array) -> jax.Array:
    return jnp.dot(h.astype(cfg.dtype), p["w_in"], preferred_element_type=jnp.float32)


def _project_out(cfg: NemotronConfig, p, y: jax.Array) -> jax.Array:
    return jnp.dot(y.astype(cfg.dtype), p["w_out"], preferred_element_type=jnp.float32)


def _ffn(cfg: NemotronConfig, p, h: jax.Array, live: jax.Array):
    """h: the normalised residual stream, float32. Returns (the layer's
    output, float32, expert stats int32 [3])."""
    with jax.named_scope("moe_router"):
        idx, w = sigmoid_top_k(h, p["router"], p["router_bias"], cfg.experts_per_token)
    return shared_expert_layer(h.astype(cfg.dtype), idx, w, p,
                               routed_scale=cfg.routed_scaling,
                               first_held=cfg.first_held_expert, live=live)


def _qkv(cfg: NemotronConfig, p, h: jax.Array):
    low = h.astype(cfg.dtype)
    return tuple(jnp.einsum("td,dhk->thk", low, p[w]) for w in ("wq", "wk", "wv"))


def _head(cfg: NemotronConfig, params, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        last = rms_norm(x, params["norm_final"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(last, params["head"], preferred_element_type=jnp.float32)


def _stats(moe: jax.Array, states, resets, pages) -> jax.Array:
    return jnp.concatenate([moe, jnp.stack([states, resets, pages]).astype(jnp.int32)])


# -- decode: one token for every slot ---------------------------------------------

def _mamba_decode(cfg: NemotronConfig, p, state, h: jax.Array, live: jax.Array):
    """h [S, d]. Every live row's state and convolution window move one
    position; the other rows' stay as they were."""
    S = h.shape[0]
    with jax.named_scope("mamba"):
        z, xbc, dt = _split(cfg, _project_in(cfg, p, h))
        with jax.named_scope("ssm_conv"):
            rows = jnp.concatenate([state["conv"], xbc[:, None]], axis=1)   # [S, K, ch]
            xbc = jax.nn.silu(jnp.sum(rows * p["conv_w"], axis=1) + p["conv_b"])
            conv = jnp.where(live[:, None, None], rows[:, 1:], state["conv"])
        x, B, C = _x_b_c(cfg, xbc)
        with jax.named_scope("ssm_update"):
            dt = jax.nn.softplus(dt + p["dt_bias"])
            ssm, y = ssm_decode_update(state["ssm"], jnp.exp(dt * -jnp.exp(p["A_log"])),
                                       dt[..., None] * x, B, C, live)
            y = y + p["D"][:, None] * x
        out = _project_out(cfg, p, _gated_norm(cfg, p, y.reshape(S, -1), z))
    return out, {"ssm": ssm, "conv": conv}


def _decode_attention(cfg: NemotronConfig, p, arena, h, cursors, table, live, trash: int):
    """h [S, d] at positions ``cursors`` [S]: this token's key and value go
    in through ``table``, then each live row attends over its own pages
    (``ops.paged_attention``; a row that is not live reads nothing)."""
    S, kv, bt = h.shape[0], cfg.kv_heads, arena["k"].shape[1]
    width = table.shape[1]
    with jax.named_scope("attn_gqa"):
        q, k, v = _qkv(cfg, p, h)
        with jax.named_scope("kv_write"):
            block = cursors // bt
            # a row past the columns it was handed (dead, or past its
            # budget) writes to trash
            ids = jnp.where(block < width, jnp.take_along_axis(
                table, jnp.minimum(block, width - 1)[:, None], axis=1)[:, 0], trash)
            off = jnp.mod(cursors, bt)
            keys = arena["k"].at[ids, off].set(k.reshape(S, -1))
            vals = arena["v"].at[ids, off].set(v.reshape(S, -1))
        qb = _heads_apart(q.reshape(S, kv, cfg.n_heads // kv, cfg.head_dim), kv)
        ctx = paged_decode_attention(qb, keys, vals, table, jnp.where(live, cursors + 1, 0),
                                     scale=cfg.head_dim ** -0.5, kv_heads=kv)
        out = jnp.einsum("shd,hdm->sm", ctx.astype(cfg.dtype), p["wo"],
                         preferred_element_type=jnp.float32)
    return out, {"k": keys, "v": vals}


def decode_step(cfg: NemotronConfig, params, cache, tok: jax.Array, table: jax.Array,
                live: jax.Array, trash: int):
    """One token for every slot. ``table`` [S, view] (the block table's
    first columns), ``live`` [S] (rows that belong to a request: the others
    take no expert, read no page and keep their states). Returns (logits
    [S, vocab] float32, cache, stats int32 [STATS])."""
    cursors = cache["cursors"]
    x = params["embedding"][tok].astype(jnp.float32)
    out_cache = {"cursors": cursors + 1}
    moe = jnp.zeros((3,), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == MAMBA:
            a, out_cache[f"layer_{i}"] = _mamba_decode(cfg, layer["mamba"],
                                                       cache[f"layer_{i}"], h, live)
        elif kind == ATTENTION:
            a, out_cache[f"layer_{i}"] = _decode_attention(
                cfg, layer["attn"], cache[f"layer_{i}"], h, cursors, table, live, trash)
        else:
            a, st = _ffn(cfg, layer["moe"], h, live)
            moe = moe + st
        x = x + a
    rows = jnp.sum(live, dtype=jnp.int32) * cfg.pattern.count(MAMBA)
    # the pages each live row's attention fetches, within the view
    bt = next((cache[f"layer_{i}"]["k"].shape[1] for i, kind in enumerate(cfg.pattern)
               if kind == ATTENTION), 0)
    pages = jnp.sum(jnp.where(live, jnp.minimum(cursors // bt + 1, table.shape[1]), 0)) \
        if bt else 0
    return _head(cfg, params, x), out_cache, _stats(moe, rows, 0, pages)


# -- prefill: one chunk of one prompt -------------------------------------------

def _mamba_chunk(cfg: NemotronConfig, p, state, h: jax.Array, slot, start, n_valid):
    """h [C, d] at positions ``start + i`` of the prompt in ``slot``, the
    first ``n_valid`` real. The slot's state and window are zeros at the
    prompt's first chunk and the previous chunk's otherwise; the real
    positions' last state and last convolution inputs go back to the slot."""
    C = h.shape[0]
    K = cfg.conv_kernel
    fresh = start == 0
    with jax.named_scope("mamba"):
        z, xbc, dt = _split(cfg, _project_in(cfg, p, h))
        with jax.named_scope("ssm_conv"):
            window = jnp.where(fresh, 0.0, state["conv"][slot])             # [K - 1, ch]
            rows = jnp.concatenate([window, xbc])                          # [K - 1 + C, ch]
            conv = sum(rows[j:j + C] * p["conv_w"][j] for j in range(K)) + p["conv_b"]
            xbc = jax.nn.silu(conv)
            window = jax.lax.dynamic_slice_in_dim(rows, n_valid, K - 1)
        x, B, Cm = _x_b_c(cfg, xbc)
        with jax.named_scope("ssm_scan"):
            real = jnp.arange(C) < n_valid
            dt = jnp.where(real[:, None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
            s0 = jnp.where(fresh, 0.0, state["ssm"][slot])
            y, last = ssd_scan(x, dt, -jnp.exp(p["A_log"]), B, Cm,
                               s0.reshape(cfg.mamba_heads, cfg.mamba_head_dim, -1),
                               cfg.ssm_chunk)
            y = y + p["D"][:, None] * x
        out = _project_out(cfg, p, _gated_norm(cfg, p, y.reshape(C, -1), z))
    return out, {"ssm": state["ssm"].at[slot].set(last.reshape(s0.shape)),
                 "conv": state["conv"].at[slot].set(window)}


def _chunk_attention(cfg: NemotronConfig, p, arena, h, start, read, write):
    """h [C, d] at positions ``start + i``: the chunk's keys and values go
    into the blocks ``write`` names, then its queries attend over the row's
    view ``read`` (``ops.chunk_attention``, causal by position)."""
    C, kv = h.shape[0], cfg.kv_heads
    g, bt = cfg.n_heads // kv, arena["k"].shape[1]
    positions = start + jnp.arange(C)
    with jax.named_scope("attn_gqa"):
        q, k, v = _qkv(cfg, p, h)
        with jax.named_scope("kv_write"):
            keys = arena["k"].at[write].set(k.reshape(C // bt, bt, -1))
            vals = arena["v"].at[write].set(v.reshape(C // bt, bt, -1))
        view = read.shape[0]
        rows = jnp.swapaxes(q.reshape(C, kv, g, cfg.head_dim), 0, 1).reshape(
            kv, C * g, cfg.head_dim)
        ctx = chunk_attention(
            rows, jnp.swapaxes(keys[read].reshape(view * bt, kv, cfg.head_dim), 0, 1),
            jnp.swapaxes(vals[read].reshape(view * bt, kv, cfg.head_dim), 0, 1),
            jnp.repeat(positions, g), jnp.arange(view * bt), scale=cfg.head_dim ** -0.5)
        ctx = jnp.swapaxes(ctx.reshape(kv, C, g, cfg.head_dim), 0, 1)
        out = jnp.einsum("thd,hdm->tm", ctx.reshape(C, cfg.n_heads, cfg.head_dim).astype(
            cfg.dtype), p["wo"], preferred_element_type=jnp.float32)
    return out, {"k": keys, "v": vals}


def prefill_chunk(cfg: NemotronConfig, params, cache, ids: jax.Array, start, n_valid,
                  read: jax.Array, write: jax.Array, slot):
    """One chunk of one prompt: ``ids`` [C] at positions ``start ..``, of
    which the first ``n_valid`` are real, in ``slot``. ``read`` [view] is the
    row's block table (its first columns, this chunk's blocks included);
    ``write`` [C / block_t] names the arena block each block of the chunk
    goes to (trash: not kept). The cursors are the engine's to set. Returns
    (logits of position ``n_valid - 1`` [vocab] float32, cache, stats int32
    [STATS])."""
    x = params["embedding"][ids].astype(jnp.float32)
    live = jnp.arange(ids.shape[0]) < n_valid
    out_cache = {"cursors": cache["cursors"]}
    moe = jnp.zeros((3,), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        h = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == MAMBA:
            a, out_cache[f"layer_{i}"] = _mamba_chunk(
                cfg, layer["mamba"], cache[f"layer_{i}"], h, slot, start, n_valid)
        elif kind == ATTENTION:
            a, out_cache[f"layer_{i}"] = _chunk_attention(
                cfg, layer["attn"], cache[f"layer_{i}"], h, start, read, write)
        else:
            a, st = _ffn(cfg, layer["moe"], h, live)
            moe = moe + st
        x = x + a
    last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=0, keepdims=False)
    return (_head(cfg, params, last), out_cache,
            _stats(moe, cfg.pattern.count(MAMBA), start == 0, 0))
