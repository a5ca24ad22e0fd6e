"""Operations and bytes of the MiMo cell's work, from shapes and from the
program's own counters: the same work whatever implements it.

``s`` is the sizes dict of ``runners/mimo_serve.sizes_of``. Only
matrix-multiply work is counted (2 FLOPs per multiply-add); norms, rotary,
softmax, the router's top-k and the sort are left out. An expert's work is
counted per ASSIGNMENT that landed on a held expert (the program's counter
``expert_tokens``), never per token: what the experts held elsewhere would
have done is nobody's work here.
"""

from __future__ import annotations

from typing import Any, Dict

FULL, WINDOW = 0, 1


def attention_params(s: Dict[str, Any], kind: int) -> int:
    """q, k, v and output projections of one attention layer."""
    kv = s["kv_heads_window"] if kind == WINDOW else s["kv_heads_full"]
    d, h = s["d_model"], s["n_heads"]
    return d * h * s["qk_dim"] + d * kv * s["qk_dim"] + d * kv * s["v_dim"] + h * s["v_dim"] * d


def expert_params(s: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * s["d_model"] * s["d_ff_expert"]


def dense_params(s: Dict[str, Any]) -> int:
    """What every token multiplies by, all layers: attention projections,
    dense SwiGLUs, routers. Without experts, embedding and head."""
    total = 0
    for kind, moe in zip(s["layer_kinds"], s["moe_layers"]):
        total += attention_params(s, kind)
        total += (s["d_model"] * s["n_experts"] if moe
                  else 3 * s["d_model"] * s["d_ff_dense"])
    return total


def head_params(s: Dict[str, Any]) -> int:
    return s["d_model"] * s["vocab_size"]


def attention_flops(s: Dict[str, Any], position: int) -> float:
    """Scores and context of ONE query token at ``position`` (0-based), all
    layers: a full layer sees ``position + 1`` keys, a window layer at most
    ``window``; per key ``2 * heads * (qk + v)``."""
    per_key = 2.0 * s["n_heads"] * (s["qk_dim"] + s["v_dim"])
    out = 0.0
    for kind in s["layer_kinds"]:
        keys = position + 1
        out += per_key * (min(keys, s["window"]) if kind == WINDOW else keys)
    return out


def attention_flops_span(s: Dict[str, Any], first: int, count: int) -> float:
    """``attention_flops`` summed over positions ``first .. first + count - 1``."""
    per_key = 2.0 * s["n_heads"] * (s["qk_dim"] + s["v_dim"])
    last = first + count                       # keys of position p: p + 1
    full = (last * (last + 1) - first * (first + 1)) / 2.0
    w = s["window"]
    ramp_end = min(max(w - 1, first), last)    # positions < w - 1 see fewer than w keys
    window = ((ramp_end * (ramp_end + 1) - first * (first + 1)) / 2.0 if ramp_end > first else 0.0) \
        + w * (last - ramp_end)
    n_window = sum(1 for k in s["layer_kinds"] if k == WINDOW)
    return per_key * ((len(s["layer_kinds"]) - n_window) * full + n_window * window)


def prefill_flops(s: Dict[str, Any], prompt: int) -> float:
    """Forward over a prompt without its experts; only the last position
    needs the head."""
    return (2.0 * dense_params(s) * prompt + attention_flops_span(s, 0, prompt)
            + 2.0 * head_params(s))


def decode_flops(s: Dict[str, Any], prompt: int, new_tokens: int) -> float:
    """``new_tokens - 1`` single-token steps after a prompt (the first new
    token comes from prefill), without their experts."""
    steps = max(new_tokens - 1, 0)
    return (steps * 2.0 * (dense_params(s) + head_params(s))
            + attention_flops_span(s, prompt, steps))


def expert_flops(s: Dict[str, Any], held_assignments: float) -> float:
    """Gate, up and down products of every assignment on a held expert."""
    return 2.0 * expert_params(s) * held_assignments


def grouped_matmul_cost(s: Dict[str, Any], held_assignments: float,
                        experts_touched: float, weight_bytes: int = 2) -> Dict[str, float]:
    """The three grouped products of the expert layers (``moe_experts``):
    FLOPs per assignment; bytes: each TOUCHED expert's three matrices once,
    and per assignment its row in (twice: gate and up), the float32 gate and
    up rows out, the bfloat16 product in and the float32 result out."""
    d, f = s["d_model"], s["d_ff_expert"]
    rows = held_assignments * (2 * d * weight_bytes + 2 * f * 4 + f * weight_bytes + d * 4)
    return {"flops": expert_flops(s, held_assignments),
            "bytes": experts_touched * expert_params(s) * weight_bytes + rows}


def kv_bytes_per_token(s: Dict[str, Any], kind: int, elem_bytes: int = 2) -> int:
    kv = s["kv_heads_window"] if kind == WINDOW else s["kv_heads_full"]
    return kv * (s["qk_dim"] + s["v_dim"]) * elem_bytes


def decode_step_bytes(s: Dict[str, Any], live_full_tokens: float, live_window_tokens: float,
                      experts_touched: float, weight_bytes: int = 2) -> float:
    """Bytes one decode step has to read: attention, dense, router and head
    weights once, the three matrices of every expert TOUCHED in the step
    (summed over the layers), and the live keys and values of each kind in
    every layer of that kind."""
    n_window = sum(1 for k in s["layer_kinds"] if k == WINDOW)
    n_full = len(s["layer_kinds"]) - n_window
    return (weight_bytes * (dense_params(s) + head_params(s))
            + weight_bytes * expert_params(s) * experts_touched
            + n_full * kv_bytes_per_token(s, FULL) * live_full_tokens
            + n_window * kv_bytes_per_token(s, WINDOW) * live_window_tokens)
