"""Sarvam weights from ``--seed``: canonical float32 arrays, ONE LAYER AT A
TIME (an expert layer's float32 weights are 3.7 GB at the published widths
with 32 experts held; all five do not fit beside the program's), and the
program's bfloat16 tree from the same draws.

``sizes`` (``runners/sarvam_serve.sizes_of``) carries the dims. A layer's
canonical arrays: ``norm_attn``, ``norm_ffn`` [d], ``q_norm`` [nope + rope],
``kv_norm`` [latent] (ones); ``wq`` [d, H, nope + rope], ``w_dkv`` [d,
latent + rope], ``w_uk`` [H, latent, nope], ``w_uv`` [H, latent, v], ``wo``
[H, v, d]; the dense layer ``w_gate``, ``w_up`` [d, I], ``w_down`` [I, d];
an expert layer ``router`` [d, E] (all E outputs), ``router_bias`` [E] ~
N(0, 0.01), the HELD experts' ``w_gate``, ``w_up`` [held, d, f], ``w_down``
[held, f, d] (the experts held elsewhere are never drawn) and the shared
expert's ``shared_gate``, ``shared_up`` [d, s], ``shared_down`` [s, d]. The
top: ``embedding`` [V, d], ``head`` [d, V], ``norm_final`` [d]. Matrices
are N(0, 0.02 x sqrt(4096 / d)): 0.02 at the published hidden size, and at
a test's toy widths as large a share of the residual stream as there (at
0.02 a toy layer's shared expert would add a thousandth of it, and no fault
would show).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key

STD = 0.02
PUBLISHED_D = 4096
STREAM = 39
_KEEP_F32 = ("router", "router_bias")
_ROUTED = ("w_gate", "w_up", "w_down")
_SHARED = ("shared_gate", "shared_up", "shared_down")


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    """One tensor, drawn in float32 and rounded (if at all) in the same
    program: a layer is never whole in float32 unless the caller keeps it."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(key, cast, s: Dict[str, Any], moe: bool, held: int):
    """A layer's arrays, one program a tensor; ``cast(name)`` gives each
    tensor's type."""
    ks = jax.random.split(key, 14)
    d, H = s["d_model"], s["n_heads"]
    std = matrix_std(d)
    qk, r = s["qk_nope_dim"] + s["qk_rope_dim"], s["kv_lora_rank"]

    def normal(i, name, shape, sd=std):
        return _normal(ks[i], shape=shape, std=sd, dtype=cast(name))

    def ones(name, n):
        return jnp.ones((n,), cast(name))

    out = {"norm_attn": ones("norm_attn", d), "norm_ffn": ones("norm_ffn", d),
           "q_norm": ones("q_norm", qk), "kv_norm": ones("kv_norm", r),
           "wq": normal(0, "wq", (d, H, qk)),
           "w_dkv": normal(1, "w_dkv", (d, r + s["qk_rope_dim"])),
           "w_uk": normal(2, "w_uk", (H, r, s["qk_nope_dim"])),
           "w_uv": normal(3, "w_uv", (H, r, s["v_dim"])),
           "wo": normal(4, "wo", (H, s["v_dim"], d))}
    if moe:
        f, sh = s["d_ff_expert"], s["d_ff_shared"]
        out.update(router=normal(5, "router", (d, s["n_experts"])),
                   router_bias=normal(6, "router_bias", (s["n_experts"],), 0.01),
                   w_gate=normal(7, "w_gate", (held, d, f)),
                   w_up=normal(8, "w_up", (held, d, f)),
                   w_down=normal(9, "w_down", (held, f, d)),
                   shared_gate=normal(10, "shared_gate", (d, sh)),
                   shared_up=normal(11, "shared_up", (d, sh)),
                   shared_down=normal(12, "shared_down", (sh, d)))
    else:
        f = s["d_ff_dense"]
        out.update(w_gate=normal(7, "w_gate", (d, f)), w_up=normal(8, "w_up", (d, f)),
                   w_down=normal(9, "w_down", (f, d)))
    return out


def matrix_std(d: int) -> float:
    return STD * (PUBLISHED_D / d) ** 0.5


def _float32(name):
    return jnp.float32


def _program_type(name):
    return jnp.float32 if name in _KEEP_F32 else jnp.bfloat16


def layer_canonical(seed: int, s: Dict[str, Any], i: int, cast=_float32,
                    held: int = 0) -> Dict[str, jax.Array]:
    """Layer ``i``'s arrays; ``held`` experts drawn (0: the sizes'
    ``held_experts``; the tests draw an uncut layer with every expert)."""
    return _layer(jax.random.fold_in(seed_key(seed, STREAM), i), cast, s,
                  bool(s["moe_layers"][i]), held or s["held_experts"])


@functools.partial(jax.jit, static_argnames=("d", "vocab"))
def _top(key, *, d, vocab):
    k1, k2 = jax.random.split(key)
    return {"embedding": jax.random.normal(k1, (vocab, d), jnp.float32) * matrix_std(d),
            "head": jax.random.normal(k2, (d, vocab), jnp.float32) * matrix_std(d),
            "norm_final": jnp.ones((d,), jnp.float32)}


def top_canonical(seed: int, s: Dict[str, Any]) -> Dict[str, jax.Array]:
    return _top(jax.random.fold_in(seed_key(seed, STREAM), 1 << 20),
                d=s["d_model"], vocab=s["vocab_size"])


@jax.jit
def _to_program(canon):
    return {k: a if k in _KEEP_F32 else a.astype(jnp.bfloat16) for k, a in canon.items()}


def program_layer(p: Dict[str, jax.Array], moe: bool) -> Dict[str, Any]:
    """A layer's flat arrays in the tree ``models/sarvam.py`` reads."""
    if not moe:
        layer = {k: a for k, a in p.items() if k not in _ROUTED}
        layer["mlp"] = {k: p[k] for k in _ROUTED}
        return layer
    group = ("router", "router_bias") + _ROUTED + _SHARED
    layer = {k: a for k, a in p.items() if k not in group}
    layer["moe"] = {**{k: p[k] for k in group[:5]},
                    "shared": {k.replace("shared_", "w_"): p[k] for k in _SHARED}}
    return layer


def program_tree(seed: int, s: Dict[str, Any]) -> Dict[str, Any]:
    """The tree ``models/sarvam.py`` reads, bfloat16 (router and its bias
    float32), from the canonical draws, a layer at a time."""
    layers = [program_layer(layer_canonical(seed, s, i, _program_type), bool(moe))
              for i, moe in enumerate(s["moe_layers"])]
    return {**_to_program(top_canonical(seed, s)), "layers": layers}
