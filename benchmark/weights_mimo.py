"""MiMo weights from ``--seed``: canonical float32 arrays, ONE LAYER AT A
TIME (a layer's float32 weights are 2 GB at the published widths; all seven
do not fit beside activations), and the program's bfloat16 tree from the
same draws.

``sizes`` (``runners/mimo_serve.sizes_of``) carries the dims. A layer's
canonical arrays: ``norm_attn``, ``norm_ffn`` [d] (ones); ``wq`` [d, H, qk],
``wk`` [d, K, qk], ``wv`` [d, K, v], ``wo`` [H, v, d]; window layers a
``sink`` [H] ~ N(ln(3/7 x window), 1), which is N(4, 1) at window 128; dense layers ``w_gate``, ``w_up`` [d, I], ``w_down``
[I, d]; expert layers ``router`` [d, E] (all E outputs), ``router_bias`` [E]
~ N(0, 0.01) and the HELD experts' ``w_gate``, ``w_up`` [held, d, f],
``w_down`` [held, f, d] (the experts held elsewhere are never drawn). The
top: ``embedding`` [V, d], ``head`` [d, V], ``norm_final`` [d]. Matrices are
N(0, 0.02).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import seed_key

STD = 0.02
#: With random N(0, 0.02) projections a window's 128 scores are nearly equal,
#: so their exponentials sum to about 128: a sink ~ N(0, 1) would take under
#: a hundredth of the mass, and a model WITHOUT sinks read closer to the
#: reference than the bfloat16 program does (0.06-0.11 sd against 0.07-0.18,
#: chip readings of PR 28). So the sinks are drawn around the value that
#: takes SINK_SHARE of a full window's mass, ln(share / (1 - share) x window)
#: = 4.0 at window 128, with sd 1: a fifth to a half, as a learned sink does.
#: Drawn so that it matters, like the router's bias.
SINK_SHARE = 0.3
STREAM = 28
_KEEP_F32 = ("router", "router_bias", "sink")


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype", "mean"))
def _normal(key, *, shape, std, dtype, mean=0.0):
    """One tensor, drawn in float32 and rounded (if at all) in the same
    program: a layer is never whole in float32 unless the caller keeps it."""
    return (jax.random.normal(key, shape, jnp.float32) * std + mean).astype(dtype)


def sink_mean(window: int) -> float:
    return round(math.log(SINK_SHARE / (1.0 - SINK_SHARE) * window), 3)


def _layer(key, cast, *, d, heads, kv, qk, v, window, moe, ff, experts, held):
    """A layer's arrays, one program a tensor (the draws of one jitted
    function of the whole layer, which held 6 GB of its own temporaries at
    the published widths); ``cast(name)`` gives each tensor's type."""
    ks = jax.random.split(key, 10)

    def normal(i, name, shape, std=STD, mean=0.0):
        return _normal(ks[i], shape=shape, std=std, dtype=cast(name), mean=mean)

    out = {"norm_attn": jnp.ones((d,), cast("norm_attn")),
           "norm_ffn": jnp.ones((d,), cast("norm_ffn")),
           "wq": normal(0, "wq", (d, heads, qk)), "wk": normal(1, "wk", (d, kv, qk)),
           "wv": normal(2, "wv", (d, kv, v)), "wo": normal(3, "wo", (heads, v, d))}
    if window:
        out["sink"] = normal(4, "sink", (heads,), 1.0, sink_mean(window))
    if moe:
        out.update(router=normal(5, "router", (d, experts)),
                   router_bias=normal(6, "router_bias", (experts,), 0.01),
                   w_gate=normal(7, "w_gate", (held, d, ff)),
                   w_up=normal(8, "w_up", (held, d, ff)),
                   w_down=normal(9, "w_down", (held, ff, d)))
    else:
        out.update(w_gate=normal(7, "w_gate", (d, ff)), w_up=normal(8, "w_up", (d, ff)),
                   w_down=normal(9, "w_down", (ff, d)))
    return out


def _float32(name):
    return jnp.float32


def _program_type(name):
    return jnp.float32 if name in _KEEP_F32 else jnp.bfloat16


def layer_canonical(seed: int, s: Dict[str, Any], i: int, cast=_float32) -> Dict[str, jax.Array]:
    window, moe = bool(s["layer_kinds"][i]), bool(s["moe_layers"][i])
    return _layer(jax.random.fold_in(seed_key(seed, STREAM), i), cast, d=s["d_model"],
                  heads=s["n_heads"], kv=s["kv_heads_window"] if window else s["kv_heads_full"],
                  qk=s["qk_dim"], v=s["v_dim"], window=s["window"] if window else 0, moe=moe,
                  ff=s["d_ff_expert"] if moe else s["d_ff_dense"],
                  experts=s["n_experts"], held=s["held_experts"])


@functools.partial(jax.jit, static_argnames=("d", "vocab"))
def _top(key, *, d, vocab):
    k1, k2 = jax.random.split(key)
    return {"embedding": jax.random.normal(k1, (vocab, d), jnp.float32) * STD,
            "head": jax.random.normal(k2, (d, vocab), jnp.float32) * STD,
            "norm_final": jnp.ones((d,), jnp.float32)}


def top_canonical(seed: int, s: Dict[str, Any]) -> Dict[str, jax.Array]:
    return _top(jax.random.fold_in(seed_key(seed, STREAM), 1 << 20),
                d=s["d_model"], vocab=s["vocab_size"])


_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down")


@jax.jit
def _to_program(canon):
    return {k: a if k in _KEEP_F32 else a.astype(jnp.bfloat16) for k, a in canon.items()}


def program_tree(seed: int, s: Dict[str, Any]) -> Dict[str, Any]:
    """The tree ``models/mimo.py`` reads, bfloat16 (router, its bias and the
    sinks float32), from the canonical draws, a layer at a time."""
    layers = []
    for i in range(len(s["layer_kinds"])):
        p = layer_canonical(seed, s, i, _program_type)
        group = "moe" if s["moe_layers"][i] else "mlp"
        keys = _MOE if group == "moe" else _MOE[2:]
        layer = {k: a for k, a in p.items() if k not in _MOE}
        layer[group] = {k: p[k] for k in keys}
        layers.append(layer)
    return {**_to_program(top_canonical(seed, s)), "layers": layers}
