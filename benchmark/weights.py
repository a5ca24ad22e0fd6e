"""Weights from ``--seed``, made by the benchmark on the device in one
jitted call, in the type the program keeps them in (float32).

The benchmark owns the weights: the program under test is handed them in
its own tree layout (``gpt_tree``, ``composite_tree``) and the plain
reference reads the canonical arrays directly, so neither side takes
anything the other has made.

Canonical GPT arrays (``L`` layers, ``h`` heads of ``k`` = d / h):
``embedding [V, d]``, ``wq wk wv [L, d, h, k]``, ``wo [L, h, k, d]``,
``w_up [L, d, ff]``, ``w_down [L, ff, d]``, and LayerNorm ``*_scale`` /
``*_bias`` ``[L, d]`` (``ln_final_*``: ``[d]``). Matrices are N(0, 0.02)
(GPT-2's ``initializer_range``), norms start at scale 1, bias 0.

Canonical composite arrays: ``embed [V, d]``, ``wqkv [L, d, 3, d]``,
``wo [L, d, d]``, ``w1 [L, d, ff]``, ``w2 [L, ff, d]``, ``ln1_scale``,
``ln2_scale [L, d]``; matrices N(0, 1/fan_in) as ``composite.init_params``
draws them, so that lr 1e-4 trains as PR 21 found it to.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

GPT_INIT_STD = 0.02


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits (``PRNGKey`` takes
    32 signed bits; the driver's seeds pass 2**31)."""
    seed = int(seed)
    hi = ((seed >> 32) ^ (stream * 0x9E3779B1)) & 0xFFFFFFFF
    return jnp.asarray(np.array([hi, seed & 0xFFFFFFFF], np.uint32))


def _gpt_canonical(key: jax.Array, *, vocab: int, d: int, layers: int,
                   heads: int, ff: int) -> Dict[str, jax.Array]:
    k = d // heads
    ks = jax.random.split(key, 7)

    def normal(kk, shape):
        return jax.random.normal(kk, shape, jnp.float32) * GPT_INIT_STD

    ones, zeros = jnp.ones((layers, d), jnp.float32), jnp.zeros((layers, d), jnp.float32)
    return {
        "embedding": normal(ks[0], (vocab, d)),
        "wq": normal(ks[1], (layers, d, heads, k)),
        "wk": normal(ks[2], (layers, d, heads, k)),
        "wv": normal(ks[3], (layers, d, heads, k)),
        "wo": normal(ks[4], (layers, heads, k, d)),
        "w_up": normal(ks[5], (layers, d, ff)),
        "w_down": normal(ks[6], (layers, ff, d)),
        "ln_attn_scale": ones, "ln_attn_bias": zeros,
        "ln_mlp_scale": ones, "ln_mlp_bias": zeros,
        "ln_final_scale": jnp.ones((d,), jnp.float32),
        "ln_final_bias": jnp.zeros((d,), jnp.float32),
    }


def gpt_canonical(seed: int, sizes: Dict[str, int]) -> Dict[str, jax.Array]:
    fn = jax.jit(_gpt_canonical, static_argnames=("vocab", "d", "layers", "heads", "ff"))
    return fn(seed_key(seed), vocab=sizes["vocab_size"], d=sizes["n_embd"],
              layers=sizes["n_layer"], heads=sizes["n_head"], ff=sizes["n_inner"])


def gpt_tree(canon: Dict[str, Any], scan_blocks: bool) -> Dict[str, Any]:
    """Canonical arrays in ``GptLM``'s parameter tree: layer-stacked under
    ``blocks`` for the scanned training layout, ``block_<i>`` otherwise.
    Works on arrays and on anything with the same leading-axis indexing
    (per-layer norms), so it also maps readings back for comparison."""

    def block(pick):
        return {
            "attention": {
                "query": {"kernel": pick(canon["wq"])},
                "key": {"kernel": pick(canon["wk"])},
                "value": {"kernel": pick(canon["wv"])},
                "out_proj": {"kernel": pick(canon["wo"])},
            },
            "ln_attn": {"scale": pick(canon["ln_attn_scale"]),
                        "bias": pick(canon["ln_attn_bias"])},
            "ln_mlp": {"scale": pick(canon["ln_mlp_scale"]),
                       "bias": pick(canon["ln_mlp_bias"])},
            "mlp": {"up_proj": {"kernel": pick(canon["w_up"])},
                    "down_proj": {"kernel": pick(canon["w_down"])}},
        }

    tree: Dict[str, Any] = {
        "embedding": {"embedding": canon["embedding"]},
        "ln_final": {"scale": canon["ln_final_scale"],
                     "bias": canon["ln_final_bias"]},
    }
    if scan_blocks:
        tree["blocks"] = block(lambda a: a)
    else:
        layers = canon["wq"].shape[0]
        for i in range(layers):
            tree[f"block_{i}"] = block(lambda a, i=i: a[i])
    return tree


def gpt_canonical_from_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of ``gpt_tree`` for the scanned layout (training readings)."""
    b = tree["blocks"]
    return {
        "embedding": tree["embedding"]["embedding"],
        "wq": b["attention"]["query"]["kernel"],
        "wk": b["attention"]["key"]["kernel"],
        "wv": b["attention"]["value"]["kernel"],
        "wo": b["attention"]["out_proj"]["kernel"],
        "w_up": b["mlp"]["up_proj"]["kernel"],
        "w_down": b["mlp"]["down_proj"]["kernel"],
        "ln_attn_scale": b["ln_attn"]["scale"], "ln_attn_bias": b["ln_attn"]["bias"],
        "ln_mlp_scale": b["ln_mlp"]["scale"], "ln_mlp_bias": b["ln_mlp"]["bias"],
        "ln_final_scale": tree["ln_final"]["scale"],
        "ln_final_bias": tree["ln_final"]["bias"],
    }


def _composite_canonical(key: jax.Array, *, vocab: int, d: int, layers: int,
                         ff: int) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    return {
        "embed": jax.random.normal(ks[4], (vocab, d), jnp.float32) * s,
        "wqkv": jax.random.normal(ks[0], (layers, d, 3, d), jnp.float32) * s,
        "wo": jax.random.normal(ks[1], (layers, d, d), jnp.float32) * s,
        "w1": jax.random.normal(ks[2], (layers, d, ff), jnp.float32) * s,
        "w2": jax.random.normal(ks[3], (layers, ff, d), jnp.float32) * ff ** -0.5,
        "ln1_scale": jnp.ones((layers, d), jnp.float32),
        "ln2_scale": jnp.ones((layers, d), jnp.float32),
    }


def composite_canonical(seed: int, sizes: Dict[str, int], out_shardings: Any = None):
    fn = jax.jit(_composite_canonical, static_argnames=("vocab", "d", "layers", "ff"),
                 out_shardings=out_shardings)
    return fn(seed_key(seed), vocab=sizes["vocab_size_run"], d=sizes["n_embd"],
              layers=sizes["n_layer"], ff=sizes["n_inner"])


def composite_tree(canon: Dict[str, Any]) -> Dict[str, Any]:
    """Canonical arrays in ``composite``'s tree for ONE pipeline chunk
    (pipe = 1, no virtual stages): a leading chunk axis of one."""
    return {"embed": canon["embed"],
            "stages": {k: canon[k][None] for k in
                       ("ln1_scale", "ln2_scale", "wqkv", "wo", "w1", "w2")}}


def composite_canonical_from_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    out = {k: v[0] for k, v in tree["stages"].items()}
    out["embed"] = tree["embed"]
    return out
