"""A toy MiMo cell for the CPU rehearsal: the ``mimo_serve`` runner, the
open-loop generator and the readers at sizes a test can hold. The limit
here was read at THESE sizes on the CPU (two seeds: program 0.006-0.015,
float8 control 0.24-0.32, one expert too few 0.095-0.099, no sink 1.0-1.3,
no window 2.7-3.4) and is not the chip's."""

from benchmark import harness

from . import toy

CONFIG = {
    "vocab_size": 2048, "hidden_size": 128, "num_attention_heads": 8, "head_dim": 24,
    "v_head_dim": 16, "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "sliding_window": 8, "rotary_dim_run": 8, "rope_theta": 1e7, "swa_rope_theta": 1e4,
    "attention_value_scale": 0.707, "hybrid_layer_pattern": [0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1], "intermediate_size": 256, "moe_intermediate_size": 64,
    "n_routed_experts_published": 16, "n_routed_experts": 8, "num_experts_per_tok": 4,
    "layernorm_epsilon": 1e-5, "max_position_embeddings": 128,
    "runners": {"mimo_serve": {"slots": 4, "kv_blocks": 64,
                               "kv_block_t": 4, "max_new_tokens": 24, "prefill_chunk": 16}},
}
MIX = {"runner": "mimo_serve", "kind": "open_loop", "rate_rps": 6.0, "lead_in_s": 0.5,
       "prompt_len": {"kind": "lognormal", "median": 24, "sigma": 0.7, "min": 6, "max": 90}}
LIMITS = {"malformed_replies": 0, "served_logit_gap_sd": 0.05}


def cell(seed: int = 2**31 + 3, seconds: float = 1.5) -> harness.Cell:
    return harness.Cell("toy.mimo", 1, "toy", CONFIG, "toy", dict(MIX),
                        {"check_requests": 12, "limits": dict(LIMITS)},
                        seed, seconds, False, toy.SPEC)
