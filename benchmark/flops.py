"""Operations and bytes the algorithm needs, from shapes alone.

Only matrix-multiply work is counted (2 FLOPs per multiply-add); norms,
activations, softmax, rotary and the optimizer are left out, as is anything
recomputed (remat's second forward, the flash backward's second score
matmul). Causal attention counts the lower triangle only. These are the
numerators of ``mfu.*`` and of the kernels' rooflines; a later PR cannot
change them.
"""

from __future__ import annotations

from typing import Dict


def block_matmul_params(d_model: int, d_ff: int) -> int:
    """Weights of one transformer block that sit in matmuls: Q, K, V, O and
    the two MLP matrices (no biases in either program)."""
    return 4 * d_model * d_model + 2 * d_model * d_ff


def attention_flops_fwd(context: float, d_model: int) -> float:
    """QK^T and PV for ONE query token against ``context`` keys, one layer,
    all heads: two matmuls of 2 * context * d_model each."""
    return 4.0 * context * d_model


def train_flops_per_token(n_layers: int, d_model: int, d_ff: int, vocab: int,
                          seq: int) -> float:
    """Forward + backward model FLOPs per trained token of a causal LM with
    a (tied) output head over ``vocab``: 6 per matmul weight, plus causal
    attention (mean context (seq + 1) / 2) three times over (forward, and
    two matmuls' worth each for the two operands in backward)."""
    weights = n_layers * block_matmul_params(d_model, d_ff) + d_model * vocab
    attn = n_layers * attention_flops_fwd((seq + 1) / 2.0, d_model)
    return 6.0 * weights + 3.0 * attn


def prefill_flops(n_layers: int, d_model: int, d_ff: int, vocab: int,
                  prompt: int) -> float:
    """Forward over a prompt; only its last position needs the head."""
    per_tok = 2.0 * n_layers * block_matmul_params(d_model, d_ff)
    attn = n_layers * attention_flops_fwd((prompt + 1) / 2.0, d_model)
    return prompt * (per_tok + attn) + 2.0 * d_model * vocab


def decode_flops(n_layers: int, d_model: int, d_ff: int, vocab: int,
                 prompt: int, new_tokens: int) -> float:
    """``new_tokens`` single-token steps after a prompt (the first new
    token comes from prefill, so ``new_tokens - 1`` steps run)."""
    steps = max(new_tokens - 1, 0)
    per_tok = 2.0 * (n_layers * block_matmul_params(d_model, d_ff)
                     + d_model * vocab)
    mean_ctx = prompt + (steps + 1) / 2.0
    return steps * (per_tok + n_layers * attention_flops_fwd(mean_ctx, d_model))


def decode_step_bytes(n_layers: int, d_model: int, d_ff: int, vocab: int,
                      live_tokens: float, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read, whatever implements it: every
    matmul weight once in the compute type, plus the keys and values of the
    tokens that are live in the batch."""
    weights = n_layers * block_matmul_params(d_model, d_ff) + d_model * vocab
    return (weight_bytes * weights
            + kv_bytes * 2.0 * n_layers * d_model * live_tokens)


def flash_fwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   elem_bytes: int = 2) -> Dict[str, float]:
    """Causal flash attention forward, one call: QK^T and PV over the lower
    triangle; reads q, k, v, writes o and the f32 log-sum-exp."""
    tri = seq * (seq + 1) / 2.0
    flops = 2 * 2.0 * batch * heads * tri * head_dim
    tensor = batch * heads * seq * head_dim * elem_bytes
    return {"flops": flops, "bytes": 4.0 * tensor + 4.0 * batch * heads * seq}


def flash_bwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   elem_bytes: int = 2) -> Dict[str, float]:
    """Causal flash attention backward (dq, dk, dv together): the five
    matmuls the algorithm needs (scores once, dP, dV, dQ, dK) over the
    lower triangle; reads q, k, v, do, lse, delta and writes dq, dk, dv."""
    tri = seq * (seq + 1) / 2.0
    flops = 5 * 2.0 * batch * heads * tri * head_dim
    tensor = batch * heads * seq * head_dim * elem_bytes
    return {"flops": flops, "bytes": 7.0 * tensor + 8.0 * batch * heads * seq}


def roofline_seconds(cost: Dict[str, float], peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])
