"""Nemotron weights from ``--seed``: canonical float32 arrays, ONE LAYER AT A
TIME (an expert layer's float32 weights are 3.0 GB at the published widths
with 128 experts held), and the program's tree from the same draws.

``sizes`` (``runners/nemotron_serve.sizes_of``) carries the dims and the
layer ``pattern``. A layer's canonical arrays, by its letter: every layer
``norm`` [d] (ones). ``M``: ``w_in`` [d, 2 d_inner + 2 G N + H], ``w_out``
[d_inner, d], ``conv_w`` [K, d_inner + 2 G N] and ``conv_b`` [d_inner + 2 G
N] ~ U(-1/2, 1/2) (PyTorch's default for a depthwise convolution of width
4), ``dt_bias`` [H] (the published initialisation: ``dt`` log-uniform in
[``time_step_min``, ``time_step_max``], floored at ``time_step_floor``, and
``dt_bias = dt + log(-expm1(-dt))``, softplus's inverse), ``A_log`` [H] =
``log U(1, 16)``, ``D`` [H] ones, ``mixer_norm`` [d_inner] ones. ``*``:
``wq`` [d, H, hd], ``wk``, ``wv`` [d, kv, hd], ``wo`` [H, hd, d]. ``E``:
``router`` [d, E] (all E outputs), ``router_bias`` [E] ~ N(0, 0.01),
``latent_down`` [d, L], ``latent_up`` [L, d], the HELD experts' ``w_up``
[held, L, f] and ``w_down`` [held, f, L] (the experts held elsewhere are
never drawn), the shared expert's ``shared_up`` [d, s], ``shared_down`` [s,
d]. The top: ``embedding`` [V, d], ``head`` [d, V], ``norm_final`` [d].
Matrices are N(0, 0.02 x sqrt(4096 / d)): 0.02 at the published hidden
size, and at a test's toy widths as large a share of the residual stream.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key

STD = 0.02
PUBLISHED_D = 4096
STREAM = 41
#: kept float32 in the program: the router and the mixer's small parameters
_KEEP_F32 = ("router", "router_bias", "conv_w", "conv_b", "dt_bias", "A_log", "D")


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    """One tensor, drawn in float32 and rounded (if at all) in the same
    program: a layer is never whole in float32 unless the caller keeps it."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def matrix_std(d: int) -> float:
    return STD * (PUBLISHED_D / d) ** 0.5


def _mamba(ks, normal, s: Dict[str, Any]) -> Dict[str, jax.Array]:
    d, H, G, N = s["d_model"], s["mamba_heads"], s["n_groups"], s["ssm_state"]
    di = H * s["mamba_head_dim"]
    ch = di + 2 * G * N
    u = lambda i, shape, lo, hi: jax.random.uniform(ks[i], shape, jnp.float32, lo, hi)
    dt = jnp.exp(u(4, (H,), math.log(s["time_step_min"]), math.log(s["time_step_max"])))
    dt = jnp.maximum(dt, s["time_step_floor"])
    return {"w_in": normal(0, "w_in", (d, di + ch + H)), "w_out": normal(1, "w_out", (di, d)),
            "conv_w": u(2, (s["conv_kernel"], ch), -0.5, 0.5), "conv_b": u(3, (ch,), -0.5, 0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)), "A_log": jnp.log(u(5, (H,), 1.0, 16.0)),
            "D": jnp.ones((H,), jnp.float32), "mixer_norm": jnp.ones((di,), jnp.float32)}


def _layer(key, cast, s: Dict[str, Any], kind: str, held: int):
    """A layer's arrays, one program a tensor; ``cast(name)`` gives each
    matrix's type."""
    ks = jax.random.split(key, 12)
    d = s["d_model"]
    std = matrix_std(d)

    def normal(i, name, shape, sd=std):
        return _normal(ks[i], shape=shape, std=sd, dtype=cast(name))

    out = {"norm": jnp.ones((d,), cast("norm"))}
    if kind == "M":
        out.update(_mamba(ks, normal, s))
        out["mixer_norm"] = out["mixer_norm"].astype(cast("mixer_norm"))
    elif kind == "*":
        H, kv, hd = s["n_heads"], s["kv_heads"], s["head_dim"]
        out.update(wq=normal(0, "wq", (d, H, hd)), wk=normal(1, "wk", (d, kv, hd)),
                   wv=normal(2, "wv", (d, kv, hd)), wo=normal(3, "wo", (H, hd, d)))
    else:
        L, f, sh = s["moe_latent"], s["d_ff_expert"], s["d_ff_shared"]
        out.update(router=normal(0, "router", (d, s["n_experts"])),
                   router_bias=normal(1, "router_bias", (s["n_experts"],), 0.01),
                   latent_down=normal(2, "latent_down", (d, L)),
                   latent_up=normal(3, "latent_up", (L, d)),
                   w_up=normal(4, "w_up", (held, L, f)),
                   w_down=normal(5, "w_down", (held, f, L)),
                   shared_up=normal(6, "shared_up", (d, sh)),
                   shared_down=normal(7, "shared_down", (sh, d)))
    return out


def _float32(name):
    return jnp.float32


def _program_type(name):
    return jnp.float32 if name in _KEEP_F32 else jnp.bfloat16


def layer_canonical(seed: int, s: Dict[str, Any], i: int, cast=_float32,
                    held: int = 0) -> Dict[str, jax.Array]:
    """Layer ``i``'s arrays; ``held`` experts drawn (0: the sizes'
    ``held_experts``; the tests draw an uncut layer with every expert)."""
    return _layer(jax.random.fold_in(seed_key(seed, STREAM), i), cast, s,
                  s["pattern"][i], held or s["held_experts"])


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


def program_layer(p: Dict[str, jax.Array], kind: str) -> Dict[str, Any]:
    """A layer's flat arrays in the tree ``models/nemotron.py`` reads."""
    rest = {k: a for k, a in p.items() if k != "norm"}
    if kind == "M":
        rest["norm"] = rest.pop("mixer_norm")
        return {"norm": p["norm"], "mamba": rest}
    if kind == "*":
        return {"norm": p["norm"], "attn": rest}
    shared = {"w_up": rest.pop("shared_up"), "w_down": rest.pop("shared_down")}
    return {"norm": p["norm"], "moe": {**rest, "shared": shared}}


def program_tree(seed: int, s: Dict[str, Any]) -> Dict[str, Any]:
    """The tree ``models/nemotron.py`` reads, bfloat16 (the router, its
    bias and the mixer's small parameters float32), from the canonical
    draws, a layer at a time."""
    layers = [program_layer(layer_canonical(seed, s, i, _program_type), kind)
              for i, kind in enumerate(s["pattern"])]
    return {**_to_program(top_canonical(seed, s)), "layers": layers}
