"""Plain FSDP (ZeRO-3) GPT train step with overlapped weight gathers.

``parallel/composite.py`` proved the ``gather_mode="overlap"`` idiom inside
the full dp x fsdp x tp x pp composition: the per-layer weight all_gather
is prefetched one layer ahead in a double-buffered ``lax.scan`` carry, so
the collective has no data dependence on the current layer's matmuls and
the compiler overlaps them (async collectives on TPU). This module applies
the same idiom to the common single-axis case — the "plain" FSDP job the
bench runs when there is no tensor or pipeline parallelism: one ``fsdp``
mesh axis shared by the batch and the weight shards, weights gathered at
use, gradients transposed into reduce_scatters by autodiff (the ZeRO-3
contract).

Modes (:data:`FSDP_GATHER_MODES`):

- ``"eager"``   — gather each layer's weights right before use (baseline;
  the gather sits on the critical path in front of every layer),
- ``"overlap"`` — double-buffered prefetch, one layer ahead; the final
  iteration prefetches a clamped duplicate that is discarded.

Both modes are numerically identical (same math, different comm placement)
— tests/test_fsdp.py asserts the parity. The autotuner
(``training/autotune.py``) sweeps this knob for multi-device GPT configs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_FSDP

FSDP_GATHER_MODES = ("eager", "overlap")


@dataclass(frozen=True)
class FsdpConfig:
    vocab_size: int = 256
    d_model: int = 32
    n_heads: int = 4
    d_ff: int = 64
    n_layers: int = 4
    seq: int = 16


def _block_specs() -> Dict[str, P]:
    """Layer-stacked [L, ...] weight shards: the largest non-layer dim goes
    over ``fsdp`` (ZeRO-3); layernorm scales are tiny and stay replicated."""
    return {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wqkv": P(None, AXIS_FSDP, None, None),   # [L, d, 3, d]
        "wo": P(None, None, AXIS_FSDP),           # [L, d, d]
        "w1": P(None, AXIS_FSDP, None),           # [L, d, ff]
        "w2": P(None, None, AXIS_FSDP),           # [L, ff, d]
    }


def fsdp_mesh(devices=None) -> Mesh:
    """A single-axis ``fsdp`` mesh over all (or the given) devices — the
    plain data-parallel/ZeRO-3 topology."""
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devs), (AXIS_FSDP,))


def init_fsdp_params(rng: jax.Array, cfg: FsdpConfig, mesh: Mesh) -> Dict[str, Any]:
    d, ff, nl = cfg.d_model, cfg.d_ff, cfg.n_layers
    ks = jax.random.split(rng, 5)
    scale = d ** -0.5
    blocks = {
        "ln1": jnp.ones((nl, d), jnp.float32),
        "ln2": jnp.ones((nl, d), jnp.float32),
        "wqkv": jax.random.normal(ks[0], (nl, d, 3, d), jnp.float32) * scale,
        "wo": jax.random.normal(ks[1], (nl, d, d), jnp.float32) * scale,
        "w1": jax.random.normal(ks[2], (nl, d, ff), jnp.float32) * scale,
        "w2": jax.random.normal(ks[3], (nl, ff, d), jnp.float32) * (ff ** -0.5),
    }
    specs = _block_specs()
    blocks = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in blocks.items()}
    embed = jax.device_put(
        jax.random.normal(ks[4], (cfg.vocab_size, d), jnp.float32) * scale,
        NamedSharding(mesh, P(AXIS_FSDP, None)))
    return {"embed": embed, "blocks": blocks}


def fsdp_param_shardings(cfg: FsdpConfig, mesh: Mesh) -> Dict[str, Any]:
    specs = _block_specs()
    return {
        "embed": NamedSharding(mesh, P(AXIS_FSDP, None)),
        "blocks": {k: NamedSharding(mesh, s) for k, s in specs.items()},
    }


def _gather_layer(wqkv_l, wo_l, w1_l, w2_l):
    """all_gather one layer's fsdp shards to full size; autodiff transposes
    each tiled gather into a gradient reduce_scatter (ZeRO-3)."""
    return (
        lax.all_gather(wqkv_l, AXIS_FSDP, axis=0, tiled=True),
        lax.all_gather(wo_l, AXIS_FSDP, axis=1, tiled=True),
        lax.all_gather(w1_l, AXIS_FSDP, axis=0, tiled=True),
        lax.all_gather(w2_l, AXIS_FSDP, axis=1, tiled=True),
    )


def _block(cfg: FsdpConfig, h, ln1, ln2, wqkv, wo, w1, w2):
    """One pre-LN transformer block, weights fully gathered (no tp axis)."""

    def ln(x, scale):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5) * scale

    x = ln(h, ln1)
    qkv = jnp.einsum("bsd,drh->bsrh", x, wqkv)           # [b, s, 3, d]
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    hd = cfg.d_model // cfg.n_heads
    b, s, _ = q.shape
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    scores = (q @ k.transpose(0, 1, 3, 2)) * (hd ** -0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, cfg.d_model)
    h = h + attn @ wo
    x = ln(h, ln2)
    return h + jax.nn.gelu(x @ w1) @ w2


def _stack_fn(cfg: FsdpConfig, p: Dict[str, jax.Array], h: jax.Array,
              *, gather_mode: str) -> jax.Array:
    """The layer stack under shard_map: ``p`` leaves are LOCAL shards
    [L, ...]; ``h`` is the local batch slice [b_local, seq, d]."""
    lns = (p["ln1"], p["ln2"])
    ws = (p["wqkv"], p["wo"], p["w1"], p["w2"])
    nl = p["ln1"].shape[0]

    if gather_mode == "overlap":

        def gather_at(i):
            return _gather_layer(
                *(lax.dynamic_index_in_dim(w, i, keepdims=False) for w in ws))

        def body(carry, i):
            h, g = carry
            # Issue layer i+1's gathers BEFORE touching layer i's weights:
            # no data dependence on the block compute, so the collectives
            # run concurrently with the matmuls. The last iteration
            # prefetches a clamped duplicate that is discarded.
            g_next = gather_at(jnp.minimum(i + 1, nl - 1))
            ln1, ln2 = (lax.dynamic_index_in_dim(s, i, keepdims=False)
                        for s in lns)
            h = _block(cfg, h, ln1, ln2, *g)
            return (h, g_next), None

        (h, _), _ = lax.scan(body, (h, gather_at(0)), jnp.arange(nl))
        return h

    def block(h, layer):
        ln1, ln2, wqkv_l, wo_l, w1_l, w2_l = layer
        wqkv, wo, w1, w2 = _gather_layer(wqkv_l, wo_l, w1_l, w2_l)
        return _block(cfg, h, ln1, ln2, wqkv, wo, w1, w2), None

    h, _ = lax.scan(block, h, lns + ws)
    return h


def make_fsdp_train_step(cfg: FsdpConfig, mesh: Mesh, lr: float = 0.1,
                         *, gather_mode: str = "overlap"):
    """jit-able (params, ids[B, seq]) -> (params, loss): one SGD step of
    next-token CE under plain ZeRO-3. The batch and the weight shards live
    on the same ``fsdp`` axis; ``gather_mode`` picks where the per-layer
    all_gathers run (see module docstring)."""
    if gather_mode not in FSDP_GATHER_MODES:
        raise ValueError(
            f"gather_mode must be one of {FSDP_GATHER_MODES}, got {gather_mode!r}")
    specs = _block_specs()
    h_spec = P(AXIS_FSDP, None, None)

    stack = jax.shard_map(
        lambda p, hh: _stack_fn(cfg, p, hh, gather_mode=gather_mode),
        mesh=mesh,
        in_specs=(specs, h_spec),
        out_specs=h_spec,
        check_vma=False,
    )

    def loss_fn(params, ids):
        # GSPMD region: embedding lookup + loss head; the layer stack is
        # manual SPMD inside the shard_map.
        h = jnp.take(params["embed"], ids, axis=0)       # [B, s, d]
        h = stack(params["blocks"], h)
        logits = h @ params["embed"].T                   # [B, s, vocab]
        targets = jnp.roll(ids, -1, axis=-1)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    def step(params, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    in_sharding = (fsdp_param_shardings(cfg, mesh),
                   NamedSharding(mesh, P(AXIS_FSDP, None)))
    return jax.jit(step, in_shardings=in_sharding,
                   out_shardings=(in_sharding[0], NamedSharding(mesh, P())))


def fsdp_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS_FSDP, None))
