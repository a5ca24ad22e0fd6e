"""Operations and bytes of the Sarvam cell's work, from shapes and from the
program's own counters: the same work whatever implements it.

``s`` is the sizes dict of ``runners/sarvam_serve.sizes_of``. Only
matrix-multiply work is counted (2 FLOPs per multiply-add); norms, rotary,
softmax, the router's top-k and the sort are left out. The MODEL's work
(``prefill_flops``, ``decode_flops``: the numerators of ``mfu.serve.sarvam``)
is the EXPANDED form in both paths: every token's latent expanded once
through ``W_uk`` and ``W_uv``, and per key a head's scores over ``nope +
rope`` dims and its context over ``v``, so that a change of form (the
absorbed decode, or a prefill that re-expands) cannot raise it by itself.
An expert's work is counted per ASSIGNMENT that landed on a held expert
(the program's counter ``expert_tokens``), never per token: what the
experts held elsewhere would have done is nobody's work here. Each kernel's
work (``mla_decode_cost``, ``mla_prefill_cost``) is counted in the form
that kernel runs.
"""

from __future__ import annotations

from typing import Any, Dict

# an expert's three matrices and the grouped products over the touched
# experts are the MiMo cell's, at this configuration's widths
from .moe_cost import expert_flops, expert_params, grouped_matmul_cost  # noqa: F401

LANE = 128


def qk_dim(s: Dict[str, Any]) -> int:
    return s["qk_nope_dim"] + s["qk_rope_dim"]


def row_width(s: Dict[str, Any]) -> int:
    """An arena row as it lies: latent and rotary key, padded to whole
    lanes (640 at the published widths)."""
    return -(-(s["kv_lora_rank"] + s["qk_rope_dim"]) // LANE) * LANE


def attention_params(s: Dict[str, Any]) -> int:
    """A layer's projections a token multiplies by in the expanded form: q,
    the latent and rotary key, the latent's expansion to keys and values,
    the output."""
    d, h, r = s["d_model"], s["n_heads"], s["kv_lora_rank"]
    return (d * h * qk_dim(s) + d * (r + s["qk_rope_dim"])
            + h * r * (s["qk_nope_dim"] + s["v_dim"]) + h * s["v_dim"] * d)


def shared_params(s: Dict[str, Any]) -> int:
    return 3 * s["d_model"] * s["d_ff_shared"]


def dense_params(s: Dict[str, Any]) -> int:
    """What every token multiplies by, all layers: attention, the dense
    SwiGLU, routers and shared experts. Without the routed experts,
    embedding and head."""
    total = 0
    for moe in s["moe_layers"]:
        total += attention_params(s)
        total += (s["d_model"] * s["n_experts"] + shared_params(s) if moe
                  else 3 * s["d_model"] * s["d_ff_dense"])
    return total


def head_params(s: Dict[str, Any]) -> int:
    return s["d_model"] * s["vocab_size"]


def attention_flops_span(s: Dict[str, Any], first: int, count: int) -> float:
    """Scores and context of the queries at positions ``first .. first +
    count - 1``, causal, all layers: per key ``2 * heads * (qk + v)``."""
    per_key = 2.0 * s["n_heads"] * (qk_dim(s) + s["v_dim"])
    last = first + count                          # keys of position p: p + 1
    keys = (last * (last + 1) - first * (first + 1)) / 2.0
    return per_key * len(s["moe_layers"]) * keys


def prefill_flops(s: Dict[str, Any], prompt: int) -> float:
    """Forward over a prompt without its routed experts; only the last
    position needs the head."""
    return (2.0 * dense_params(s) * prompt + attention_flops_span(s, 0, prompt)
            + 2.0 * head_params(s))


def decode_flops(s: Dict[str, Any], prompt: int, new_tokens: int) -> float:
    """``new_tokens - 1`` single-token steps after a prompt (the first new
    token comes from prefill), without their routed experts."""
    steps = max(new_tokens - 1, 0)
    return (steps * 2.0 * (dense_params(s) + head_params(s))
            + attention_flops_span(s, prompt, steps))


def page_bytes(s: Dict[str, Any], block_t: int, elem_bytes: int = 2) -> int:
    """One page of one layer's latent arena, as it lies: ``block_t`` rows of
    ``row_width`` (1,280 B a position at the published widths, where the
    576 columns that hold something would be 1,152)."""
    return block_t * row_width(s) * elem_bytes


def decode_step_bytes(s: Dict[str, Any], experts_touched: float, pages: float,
                      block_t: int, weight_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight a token multiplies
    by once (the routers float32), the three matrices of every expert
    TOUCHED in the step (summed over the layers), and the live rows' latent
    pages, whole (``pages``: a layer's, every layer reads as many)."""
    router = sum(s["moe_layers"]) * s["d_model"] * s["n_experts"]
    return (weight_bytes * (dense_params(s) - router + head_params(s)) + 4 * router
            + weight_bytes * expert_params(s) * experts_touched
            + len(s["moe_layers"]) * page_bytes(s, block_t) * pages)


def mla_decode_cost(s: Dict[str, Any], pages: float, block_t: int) -> Dict[str, float]:
    """The absorbed decode kernel over ``pages`` pages (one layer's calls, or
    all layers' summed): every page read once, whole; a head's scores over
    the ``latent + rope`` columns of each of its positions and its context
    over the ``latent``."""
    r = s["kv_lora_rank"]
    per_position = 2.0 * s["n_heads"] * (2 * r + s["qk_rope_dim"])
    return {"flops": per_position * block_t * pages,
            "bytes": float(page_bytes(s, block_t) * pages)}


def mla_prefill_cost(s: Dict[str, Any], start: int, tokens: int) -> Dict[str, float]:
    """The expanded prefill kernel over one chunk of ``tokens`` queries at
    ``start`` (one layer): the latent row of every key the chunk sees
    expanded to every head's key and value, a head's scores over ``nope +
    rope`` dims and its context over ``v`` for every key a query sees (the
    kernel expands whole blocks of keys and scores whole tiles: what it
    runs past these is not counted); bytes: those rows read once, and the
    expansion matrices."""
    h, r = s["n_heads"], s["kv_lora_rank"]
    expanded = start + tokens
    seen = tokens * start + tokens * (tokens + 1) / 2.0
    flops = (2.0 * h * r * (s["qk_nope_dim"] + s["v_dim"]) * expanded
             + 2.0 * h * (qk_dim(s) + s["v_dim"]) * seen)
    return {"flops": flops,
            "bytes": float(expanded * row_width(s) * 2
                           + h * r * (s["qk_nope_dim"] + s["v_dim"]) * 2)}
