"""Operations and bytes of the SDAR cell's work, from shapes and from the
program's own counters: the same work whatever implements it.

``s`` is the sizes dict of ``runners/sdar_serve.sizes_of``. Only
matrix-multiply work is counted (2 FLOPs per multiply-add): the
projections, the router, a token's ``experts_per_token`` experts, the head,
and the attention's scores and context over the keys a query SEES (every
earlier block and all of its own). Norms, rotary, the softmaxes, the
confidence and the choice of positions are left out. The work of a BLOCK is
``denoise_steps + 1`` forward passes over its ``block_len`` positions (the
denoising passes and the commit), whatever the program runs: a program
that merged the commit into the next block's first pass, or revealed
faster on a threshold, would do less and is counted as doing this.
"""

from __future__ import annotations

from typing import Any, Dict

# an expert's three matrices and the grouped products over the touched
# experts are the MiMo cell's, at this configuration's widths
from .moe_cost import expert_params, grouped_matmul_cost  # noqa: F401


def attention_params(s: Dict[str, Any]) -> int:
    """q, k, v and output projections of one layer."""
    d, k = s["d_model"], s["head_dim"]
    return 2 * d * s["n_heads"] * k + 2 * d * s["kv_heads"] * k


def dense_params(s: Dict[str, Any]) -> int:
    """What every token multiplies by outside its experts, all layers."""
    return s["n_layers"] * (attention_params(s) + s["d_model"] * s["n_experts"])


def token_params(s: Dict[str, Any]) -> int:
    """What one token multiplies by in all layers: ``dense_params`` and its
    chosen experts."""
    return dense_params(s) + s["n_layers"] * s["experts_per_token"] * expert_params(s)


def head_params(s: Dict[str, Any]) -> int:
    return s["d_model"] * s["vocab_size"]


def attention_flops(s: Dict[str, Any], queries: float, keys: float) -> float:
    """Scores and context of ``queries`` positions over ``keys`` each, all
    layers."""
    return 4.0 * s["n_heads"] * s["head_dim"] * s["n_layers"] * queries * keys


def prefill_flops(s: Dict[str, Any], prompt: int) -> float:
    """Forward over a prompt's whole blocks under the block mask; no head (a
    prefill yields no token). Block ``b`` sees ``(b + 1) * B`` keys."""
    B = s["block_len"]
    blocks = prompt // B
    keys = B * B * blocks * (blocks + 1) / 2.0          # sum over blocks of B * (b + 1) * B
    return 2.0 * token_params(s) * blocks * B + attention_flops(s, 1.0, keys)


def pass_flops(s: Dict[str, Any], cursor: float) -> float:
    """One forward pass over a block of ``block_len`` positions at
    ``cursor``: projections, experts and head of every position, attention
    over ``cursor + block_len`` keys."""
    B = s["block_len"]
    return (2.0 * (token_params(s) + head_params(s)) * B
            + attention_flops(s, B, cursor + B))


def blocks_of(s: Dict[str, Any], prompt: int, new_tokens: int) -> int:
    """Blocks a request generates: the first opens with the prompt's tail."""
    B = s["block_len"]
    return -(-(prompt % B + new_tokens) // B)


def generate_flops(s: Dict[str, Any], prompt: int, new_tokens: int) -> float:
    """``denoise_steps + 1`` passes a block over the blocks that hold
    ``new_tokens`` tokens after ``prompt``."""
    B, first = s["block_len"], prompt // s["block_len"] * s["block_len"]
    return (s["denoise_steps"] + 1) * sum(
        pass_flops(s, first + B * b) for b in range(blocks_of(s, prompt, new_tokens)))


def page_bytes(s: Dict[str, Any], block_t: int, elem_bytes: int = 2) -> int:
    """One page of one layer: ``block_t`` positions' keys and values."""
    return 2 * block_t * s["kv_heads"] * s["head_dim"] * elem_bytes


def pass_bytes(s: Dict[str, Any], experts_touched: float, pages: float, block_t: int,
               weight_bytes: int = 2) -> float:
    """Bytes one pass of the whole batch has to read: attention, router and
    head weights once (the router float32), the three matrices of every
    expert TOUCHED (summed over the layers), and the live rows' pages, whole
    (``pages``: a layer's, every layer reads as many)."""
    router = s["n_layers"] * s["d_model"] * s["n_experts"]
    return (weight_bytes * (dense_params(s) - router + head_params(s)) + 4 * router
            + weight_bytes * expert_params(s) * experts_touched
            + s["n_layers"] * page_bytes(s, block_t) * pages)


def block_attention_cost(s: Dict[str, Any], pages: float, block_t: int) -> Dict[str, float]:
    """The block attention over ``pages`` pages (one layer's calls): every
    page's keys and values read once, and scores and context of
    ``block_len`` queries a head over its positions."""
    return {"flops": 4.0 * s["n_heads"] * s["head_dim"] * s["block_len"] * block_t * pages,
            "bytes": float(page_bytes(s, block_t) * pages)}
