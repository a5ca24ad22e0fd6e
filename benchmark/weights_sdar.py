"""SDAR weights from ``--seed``: canonical float32 arrays, ONE LAYER AT A
TIME and a tensor at a time (a layer's float32 weights are 2.5 GB at the
published widths, all 128 experts held), and the program's bfloat16 tree
from the same draws.

``sizes`` (``runners/sdar_serve.sizes_of``) carries the dims. A layer's
canonical arrays: ``norm_attn``, ``norm_ffn`` [d], ``norm_q``, ``norm_k``
[k] (ones); ``wq`` [d, H, k], ``wk``, ``wv`` [d, K, k], ``wo`` [H, k, d];
``router`` [d, E] (all E outputs); the HELD experts' ``w_gate``, ``w_up``
[held, d, f], ``w_down`` [held, f, d] (all of them in the cell; a share in
the CPU test of the cut). The top: ``embedding`` [V, d], ``head`` [d, V],
``norm_final`` [d] (ones). Matrices are N(0, 0.02).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key

STD = 0.02
STREAM = 35
_KEEP_F32 = ("router",)
_MOE = ("router", "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, *, shape, dtype):
    """One tensor, drawn in float32 and rounded (if at all) in the same
    program: a layer is never whole in float32 unless the caller keeps it."""
    return (jax.random.normal(key, shape, jnp.float32) * STD).astype(dtype)


def _float32(name):
    return jnp.float32


def _program_type(name):
    return jnp.float32 if name in _KEEP_F32 else jnp.bfloat16


def layer_canonical(seed: int, s: Dict[str, Any], i: int, cast=_float32) -> Dict[str, jax.Array]:
    """Layer ``i``'s arrays; ``cast(name)`` gives each tensor's type."""
    d, f, H, K, k = s["d_model"], s["d_ff_expert"], s["n_heads"], s["kv_heads"], s["head_dim"]
    held = s["held_experts"]
    ks = jax.random.split(jax.random.fold_in(seed_key(seed, STREAM), i), 8)

    def normal(j, name, shape):
        return _normal(ks[j], shape=shape, dtype=cast(name))

    ones = lambda name, n: jnp.ones((n,), cast(name))
    return {"norm_attn": ones("norm_attn", d), "norm_ffn": ones("norm_ffn", d),
            "norm_q": ones("norm_q", k), "norm_k": ones("norm_k", k),
            "wq": normal(0, "wq", (d, H, k)), "wk": normal(1, "wk", (d, K, k)),
            "wv": normal(2, "wv", (d, K, k)), "wo": normal(3, "wo", (H, k, d)),
            "router": normal(4, "router", (d, s["n_experts"])),
            "w_gate": normal(5, "w_gate", (held, d, f)), "w_up": normal(6, "w_up", (held, d, f)),
            "w_down": normal(7, "w_down", (held, f, d))}


@functools.partial(jax.jit, static_argnames=("d", "vocab", "dtype"))
def _top(key, *, d, vocab, dtype):
    k1, k2 = jax.random.split(key)
    return {"embedding": (jax.random.normal(k1, (vocab, d), jnp.float32) * STD).astype(dtype),
            "head": (jax.random.normal(k2, (d, vocab), jnp.float32) * STD).astype(dtype),
            "norm_final": jnp.ones((d,), dtype)}


def top_canonical(seed: int, s: Dict[str, Any], dtype=jnp.float32) -> Dict[str, jax.Array]:
    return _top(jax.random.fold_in(seed_key(seed, STREAM), 1 << 20),
                d=s["d_model"], vocab=s["vocab_size"], dtype=dtype)


def program_tree(seed: int, s: Dict[str, Any]) -> Dict[str, Any]:
    """The tree ``models/sdar.py`` reads, bfloat16 (the router float32), from
    the canonical draws, a layer at a time."""
    layers = []
    for i in range(s["n_layers"]):
        p = layer_canonical(seed, s, i, _program_type)
        layer = {k: a for k, a in p.items() if k not in _MOE}
        layer["moe"] = {k: p[k] for k in _MOE}
        layers.append(layer)
    return {**top_canonical(seed, s, jnp.bfloat16), "layers": layers}
