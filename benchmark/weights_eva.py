"""EvaByte weights from ``--seed``: canonical float32 arrays, ONE LAYER AT A
TIME and a tensor at a time (a layer's float32 weights are 810 MB at the
published widths), and the program's bfloat16 tree from the same draws.

``sizes`` (``runners/eva_serve.sizes_of``) carries the dims. A layer's
canonical arrays: ``norm_attn``, ``norm_ffn`` [d] (zeros: the norms' gain is
an offset from one); ``wq``, ``wk``, ``wv`` [d, H * k], ``wo`` [H * k, d];
``mu``, ``phi`` [H, k] ~ N(0, pool_std); ``w_gate``, ``w_up`` [d, f],
``w_down`` [f, d]. The top: ``embedding`` [V, d], ``head`` [d, V] (head 0 of
the release's eight), ``norm_final`` [d] (zeros). Matrices are N(0, 0.02).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key

STD = 0.02
#: With N(0, 0.02) projections of a unit-RMS input a rotated key's entries
#: have standard deviation 0.02 sqrt(d_model) (1.28 at 4,096), so a chunk's
#: pooling logits ``s k . mu`` have standard deviation 0.02 sqrt(d_model) x
#: the scale of ``mu``. Under near-zero ``mu`` and ``phi`` every chunk pools to
#: its plain mean and a program that ignored them would pass (as ISSUE 28's
#: N(0, 1) sinks let the sink fault pass); so they are drawn at the scale
#: that gives the logits a standard deviation of POOL_LOGIT_SD: the largest
#: of a chunk's 16 weights then reads 3.2 times their mean (layer 0 at the
#: published widths over 256 chunks x 32 heads of random bytes, CPU reading
#: of PR 32: logits' sd 0.795 for mu and 0.787 for phi; largest over mean
#: 3.17 and 3.15 on average, 2.1 at the 10th percentile, 4.5 at the 90th).
POOL_LOGIT_SD = 0.8
STREAM = 32
_KEEP_F32 = ("mu", "phi")


def pool_std(d_model: int) -> float:
    return POOL_LOGIT_SD / (STD * d_model ** 0.5)


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    """One tensor, drawn in float32 and rounded (if at all) in the same
    program."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _float32(name):
    return jnp.float32


def _program_type(name):
    return jnp.float32 if name in _KEEP_F32 else jnp.bfloat16


def layer_canonical(seed: int, s: Dict[str, Any], i: int, cast=_float32) -> Dict[str, jax.Array]:
    """Layer ``i``'s arrays; ``cast(name)`` gives each tensor's type."""
    d, f, heads, k = s["d_model"], s["d_ff"], s["n_heads"], s["head_dim"]
    ks = jax.random.split(jax.random.fold_in(seed_key(seed, STREAM), i), 9)

    def normal(j, name, shape, std=STD):
        return _normal(ks[j], shape=shape, std=std, dtype=cast(name))

    return {"norm_attn": jnp.zeros((d,), cast("norm_attn")),
            "norm_ffn": jnp.zeros((d,), cast("norm_ffn")),
            "wq": normal(0, "wq", (d, heads * k)), "wk": normal(1, "wk", (d, heads * k)),
            "wv": normal(2, "wv", (d, heads * k)), "wo": normal(3, "wo", (heads * k, d)),
            "mu": normal(4, "mu", (heads, k), pool_std(d)),
            "phi": normal(5, "phi", (heads, k), pool_std(d)),
            "w_gate": normal(6, "w_gate", (d, f)), "w_up": normal(7, "w_up", (d, f)),
            "w_down": normal(8, "w_down", (f, d))}


@functools.partial(jax.jit, static_argnames=("d", "vocab"))
def _top(key, *, d, vocab):
    k1, k2 = jax.random.split(key)
    return {"embedding": jax.random.normal(k1, (vocab, d), jnp.float32) * STD,
            "head": jax.random.normal(k2, (d, vocab), jnp.float32) * STD,
            "norm_final": jnp.zeros((d,), jnp.float32)}


def top_canonical(seed: int, s: Dict[str, Any]) -> Dict[str, jax.Array]:
    return _top(jax.random.fold_in(seed_key(seed, STREAM), 1 << 20),
                d=s["d_model"], vocab=s["vocab_size"])


_MLP = ("w_gate", "w_up", "w_down")


def program_tree(seed: int, s: Dict[str, Any]) -> Dict[str, Any]:
    """The tree ``models/evabyte.py`` reads, bfloat16 (``mu`` and ``phi``
    float32), from the canonical draws, a layer at a time."""
    layers = []
    for i in range(s["n_layers"]):
        p = layer_canonical(seed, s, i, _program_type)
        layer = {k: a for k, a in p.items() if k not in _MLP}
        layer["mlp"] = {k: p[k] for k in _MLP}
        layers.append(layer)
    top = {k: a.astype(jnp.bfloat16) for k, a in top_canonical(seed, s).items()}
    return {**top, "layers": layers}
