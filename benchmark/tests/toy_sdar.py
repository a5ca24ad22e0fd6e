"""A toy SDAR cell for the CPU rehearsal: the ``sdar_serve`` runner, the
open-loop generator and the readers at sizes a test can hold (blocks of 4,
4 steps, 16 experts all held, pages of 4). The limits here were read at
THESE sizes on the CPU (two seeds, 1.5 s windows, 257-260 passes a seed:
program 0.056-0.068 for the tokens and 0.007-0.016 for the choice of
position; float8 control 0.19-0.20 and 0.15-0.16; one expert too few
0.12-0.24 and 0.10-0.11; causal inside the block 1.1-1.2 and 0.42-0.46; no
commit 0.9-1.8 and 0.24-0.28; no q/k norm 1.9-2.3 and 0.42-0.65; left to
right: the tokens are the program's, the choice 0.42-0.61) and are not the
chip's."""

from benchmark import harness

from . import toy

CONFIG = {
    "vocab_size": 2048, "hidden_size": 128, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 2, "moe_intermediate_size": 64, "num_experts": 16,
    "num_experts_per_tok": 4, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "generation": {"block_length": 4, "denoising_steps": 4, "confidence_threshold": 0.9,
                   "mask_token_id": 2000},
    "runners": {"sdar_serve": {"slots": 4, "kv_blocks": 96, "kv_block_t": 4, "max_seq": 128,
                               "max_new_tokens": 22, "prefill_chunk": 16}},
}
MIX = {"runner": "sdar_serve", "kind": "open_loop", "rate_rps": 6.0, "lead_in_s": 0.5,
       "prompt_len": {"kind": "lognormal", "median": 24, "sigma": 0.7, "min": 3, "max": 90}}
LIMITS = {"malformed_replies": 0, "served_logit_gap_sd": 0.09, "reveal_choice_gap_sd": 0.05}


def cell(seed: int = 2**31 + 3, seconds: float = 1.5) -> harness.Cell:
    return harness.Cell("toy.sdar", 1, "toy", CONFIG, "toy", dict(MIX),
                        {"check_requests": 12, "limits": dict(LIMITS)},
                        seed, seconds, False, toy.SPEC)
