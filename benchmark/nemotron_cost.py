"""Operations and bytes of the Nemotron cell's work, from shapes and from the
program's own counters: the same work whatever implements it.

``s`` is the sizes dict of ``runners/nemotron_serve.sizes_of``. Only
matrix-multiply work is counted (2 FLOPs per multiply-add); norms, the
convolution, the gating, softmax, the router's top-k and the sort are left
out. The MODEL's work (``prefill_flops``, ``decode_flops``: the numerators
of ``mfu.serve.nemotron``) counts a Mamba layer's state in the RECURRENT
form in both paths: per position and head ``P x N`` multiply-adds into the
state (``dt x (outer) B``) and as many out of it (``S C``), so that the
chunked form, which does more, cannot raise it by itself. An expert's work
is counted per ASSIGNMENT that landed on a held expert (the program's
counter ``expert_tokens``), never per token. Each piece of device work
(``ssm_scan_cost``, ``ssm_state_bytes``, ``grouped_cost``) is counted in
the form that piece runs.
"""

from __future__ import annotations

from typing import Any, Dict

F32 = 4


def kinds(s: Dict[str, Any], kind: str) -> int:
    return s["pattern"].count(kind)


def d_inner(s: Dict[str, Any]) -> int:
    return s["mamba_heads"] * s["mamba_head_dim"]


def mamba_params(s: Dict[str, Any]) -> int:
    """A Mamba layer's two projections: ``W_in`` to ``[z | xBC | dt]`` and
    ``W_out``."""
    di, gn = d_inner(s), s["n_groups"] * s["ssm_state"]
    return s["d_model"] * (2 * di + 2 * gn + s["mamba_heads"]) + di * s["d_model"]


def attention_params(s: Dict[str, Any]) -> int:
    d, hd = s["d_model"], s["head_dim"]
    return 2 * d * s["n_heads"] * hd + 2 * d * s["kv_heads"] * hd


def expert_layer_params(s: Dict[str, Any]) -> int:
    """What every token of an expert layer multiplies by: the router, the
    two latent projections and the shared expert."""
    d = s["d_model"]
    return d * s["n_experts"] + 2 * d * s["moe_latent"] + 2 * d * s["d_ff_shared"]


def expert_params(s: Dict[str, Any]) -> int:
    """One routed expert's two matrices, in the latent."""
    return 2 * s["moe_latent"] * s["d_ff_expert"]


def dense_params(s: Dict[str, Any]) -> int:
    """What every token multiplies by, all layers, without the routed
    experts, embedding and head."""
    return (kinds(s, "M") * mamba_params(s) + kinds(s, "*") * attention_params(s)
            + kinds(s, "E") * expert_layer_params(s))


def head_params(s: Dict[str, Any]) -> int:
    return s["d_model"] * s["vocab_size"]


def state_flops(s: Dict[str, Any], positions: float) -> float:
    """The recurrence of every Mamba layer over ``positions`` positions:
    per position and head ``P x N`` multiply-adds into the state and as
    many out."""
    return 4.0 * kinds(s, "M") * s["mamba_heads"] * s["mamba_head_dim"] * s["ssm_state"] \
        * positions


def attention_flops_span(s: Dict[str, Any], first: int, count: int) -> float:
    """Scores and context of the queries at positions ``first .. first +
    count - 1``, causal, all attention layers: per key ``4 * heads *
    head_dim``."""
    last = first + count
    keys = (last * (last + 1) - first * (first + 1)) / 2.0
    return 4.0 * s["n_heads"] * s["head_dim"] * kinds(s, "*") * keys


def prefill_flops(s: Dict[str, Any], prompt: int) -> float:
    """Forward over a prompt without its routed experts; only the last
    position needs the head."""
    return (2.0 * dense_params(s) * prompt + state_flops(s, prompt)
            + attention_flops_span(s, 0, prompt) + 2.0 * head_params(s))


def decode_flops(s: Dict[str, Any], prompt: int, new_tokens: int) -> float:
    """``new_tokens - 1`` single-token steps after a prompt (the first new
    token comes from prefill), without their routed experts."""
    steps = max(new_tokens - 1, 0)
    return (steps * 2.0 * (dense_params(s) + head_params(s)) + state_flops(s, steps)
            + attention_flops_span(s, prompt, steps))


def expert_flops(s: Dict[str, Any], held_assignments: float) -> float:
    """Both products of every assignment on a held expert."""
    return 2.0 * expert_params(s) * held_assignments


def grouped_cost(s: Dict[str, Any], held_assignments: float, experts_touched: float,
                 weight_bytes: int = 2) -> Dict[str, float]:
    """The two grouped products of the expert layers (``moe_experts``:
    ``grouped_relu2`` and ``grouped_matmul``): FLOPs per assignment; bytes:
    each TOUCHED expert's two matrices once, and per assignment its latent
    row in, the squared product out and in again (bfloat16), the float32
    result out."""
    L, f = s["moe_latent"], s["d_ff_expert"]
    rows = held_assignments * (L * weight_bytes + 2 * f * weight_bytes + L * F32)
    return {"flops": expert_flops(s, held_assignments),
            "bytes": experts_touched * expert_params(s) * weight_bytes + rows}


def state_bytes(s: Dict[str, Any]) -> int:
    """One Mamba layer's state of one slot: ``S`` [H, P, N] float32."""
    return s["mamba_heads"] * s["mamba_head_dim"] * s["ssm_state"] * F32


def window_bytes(s: Dict[str, Any]) -> int:
    """One Mamba layer's convolution window of one slot: the last
    ``conv_kernel - 1`` inputs of every channel, float32."""
    return (s["conv_kernel"] - 1) * (d_inner(s) + 2 * s["n_groups"] * s["ssm_state"]) * F32


def ssm_state_bytes(s: Dict[str, Any], rows: float) -> float:
    """What ``ssm_decode_update`` must move for ``rows`` (live row, layer)
    pairs: the state read and written, and its inputs (``dt x`` [H, P],
    ``exp(dt A)`` [H], ``B`` and ``C`` [G, N]) and output (``S C`` [H, P])
    once, float32. The convolution window is shifted outside the kernel."""
    H, P, gn = s["mamba_heads"], s["mamba_head_dim"], s["n_groups"] * s["ssm_state"]
    return rows * (2 * state_bytes(s) + (2 * H * P + H + 2 * gn) * F32)


def ssm_scan_cost(s: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """The chunked scan (SSD) of ONE Mamba layer over a prompt chunk's
    ``tokens`` positions, in sub-chunks of ``ssm_chunk``: inside a
    sub-chunk ``C B^T`` a group and the decayed product with ``dt x`` a head
    (``Q`` keys a position), and a head's state in and out a position (``P
    x N`` each way); bytes: ``x``, ``dt``, ``B``, ``C`` read and ``y``
    written (float32), the slot's state read and written once."""
    H, P, G, N = s["mamba_heads"], s["mamba_head_dim"], s["n_groups"], s["ssm_state"]
    Q = min(s["ssm_chunk"], max(tokens, 1))
    per_position = 2.0 * Q * (G * N + H * P) + 4.0 * H * P * N
    return {"flops": per_position * tokens,
            "bytes": float(tokens * (2 * H * P + H + 2 * G * N) * F32
                           + 2 * state_bytes(s))}


def kv_page_bytes(s: Dict[str, Any], block_t: int, elem_bytes: int = 2) -> int:
    """One page of one attention layer's keys and values."""
    return 2 * block_t * s["kv_heads"] * s["head_dim"] * elem_bytes


def decode_step_bytes(s: Dict[str, Any], experts_touched: float, ssm_rows: float,
                      pages: float, block_t: int, weight_bytes: int = 2) -> float:
    """Bytes one decode step has to read or write: every weight a token
    multiplies by once (the routers float32), the two matrices of every
    expert TOUCHED in the step (summed over the layers), the states of the
    ``ssm_rows`` (live row, Mamba layer) pairs read and written with their
    convolution windows, and the live rows' KV pages, whole (``pages``: an
    attention layer's)."""
    router = kinds(s, "E") * s["d_model"] * s["n_experts"]
    return (weight_bytes * (dense_params(s) - router + head_params(s)) + F32 * router
            + weight_bytes * expert_params(s) * experts_touched
            + ssm_rows * 2 * (state_bytes(s) + window_bytes(s))
            + kinds(s, "*") * kv_page_bytes(s, block_t) * pages)
