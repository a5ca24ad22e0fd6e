"""Operations and bytes of the EvaByte cell's work, from shapes and from the
program's own dispatch stats: the same work whatever implements it.

``s`` is the sizes dict of ``runners/eva_serve.sizes_of``. Only
matrix-multiply work is counted (2 FLOPs per multiply-add): the projections,
the SwiGLU, the head, and the attention's scores and context over the keys a
query SEES (its own window's exact keys and one summary a chunk of every
earlier window). Norms, rotary, the softmax and the pooling of a chunk into
its summary (8 multiply-adds a key dim and token, a thousandth of the
projections) are left out, as is anything an implementation does beyond
the algorithm (the decode kernel multiplies every query head by every
head's keys; one head's worth is counted).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def attn_width(s: Dict[str, Any]) -> int:
    return s["n_heads"] * s["head_dim"]


def layer_params(s: Dict[str, Any]) -> int:
    """One layer's matmul weights: q, k, v, o and the SwiGLU's three."""
    d = s["d_model"]
    return 4 * d * attn_width(s) + 3 * d * s["d_ff"]


def head_params(s: Dict[str, Any]) -> int:
    return s["d_model"] * s["vocab_size"]


def keys_seen(s: Dict[str, Any], position):
    """Keys and summaries the query at ``position`` (0-based; a number or an
    array) attends to in one layer."""
    w = s["window"]
    return position % w + 1 + position // w * (w // s["chunk_size"])


def attention_flops_span(s: Dict[str, Any], first: int, count: int) -> float:
    """Scores and context of the queries at positions ``first .. first +
    count - 1``, all layers: per key seen ``2 * 2 * heads * head_dim``."""
    if count <= 0:
        return 0.0
    seen = keys_seen(s, np.arange(first, first + count, dtype=np.int64))
    return 4.0 * attn_width(s) * s["n_layers"] * float(seen.sum())


def prefill_flops(s: Dict[str, Any], prompt: int) -> float:
    """Forward over a prompt; only its last position needs the head."""
    return (2.0 * s["n_layers"] * layer_params(s) * prompt
            + attention_flops_span(s, 0, prompt) + 2.0 * head_params(s))


def decode_flops(s: Dict[str, Any], prompt: int, new_tokens: int) -> float:
    """``new_tokens - 1`` single-token steps after a prompt (the first new
    token comes from prefill)."""
    steps = max(new_tokens - 1, 0)
    return (steps * 2.0 * (s["n_layers"] * layer_params(s) + head_params(s))
            + attention_flops_span(s, prompt, steps))


def kv_bytes_per_row(s: Dict[str, Any], elem_bytes: int = 2) -> int:
    """A position's key and value (or a chunk's summary pair) in one layer."""
    return 2 * attn_width(s) * elem_bytes


def decode_step_bytes(s: Dict[str, Any], local_rows: float, summary_rows: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight once, and in
    every layer the live rows' local keys and values and their visible
    summaries."""
    return (weight_bytes * (s["n_layers"] * layer_params(s) + head_params(s))
            + s["n_layers"] * kv_bytes_per_row(s) * (local_rows + summary_rows))


def decode_attention_cost(s: Dict[str, Any], rows: float) -> Dict[str, float]:
    """The decode attention over ``rows`` cache rows (local positions and
    summaries together), one layer and step: scores and context of one query
    a head, and every row's key and value read once."""
    return {"flops": 4.0 * attn_width(s) * rows, "bytes": kv_bytes_per_row(s) * rows}
