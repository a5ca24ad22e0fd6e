"""A toy Sarvam cell for the CPU rehearsal: the ``sarvam_serve`` runner, the
open-loop generator and the readers at sizes a test can hold (4 heads of 16
+ 8 over a latent of 32, 16 experts of which 8 are held, pages of 4). The
limit here was read at THESE sizes on the CPU and is not the chip's (three
seeds, 1.5 s windows, 8 requests a seed, 160 tokens checked;
``served_off_best_pct``): the program 2.5-5.6, the reference at bfloat16
1.9-2.5, the float8 control 34.4-38.1 (over all 160), and over the 60
tokens of the 3 shortest requests no mscale 45.0-53.3, no YaRN 65.0-71.7,
no latent norm 58.3-65.0, no routed scaling 36.7-40.0, no shared expert
55.0-63.3. The widest gap alone cannot tell them apart here: the program
reads 0.02-0.93 sd, the bfloat16 reference 0.04-0.50, the control
0.90-1.51."""

from benchmark import harness

from . import toy

CONFIG = {
    "vocab_size": 2048, "hidden_size": 128, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "kv_lora_rank": 32, "v_head_dim": 16, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 256, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "num_experts": 8, "num_experts_published": 16,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                     "type": "deepseek_yarn"},
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    "runners": {"sarvam_serve": {"slots": 4, "kv_blocks": 96, "kv_block_t": 4,
                                 "max_new_tokens": 20, "prefill_chunk": 16}},
}
MIX = {"runner": "sarvam_serve", "kind": "open_loop", "rate_rps": 6.0, "lead_in_s": 0.5,
       "prompt_len": {"kind": "lognormal", "median": 40, "sigma": 0.5, "min": 10, "max": 100},
       "shared_prefix": {"share": 1.0, "length": 100, "groups": 3}}
LIMITS = {"malformed_replies": 0, "served_off_best_pct": 15.0}


def cell(seed: int = 2**31 + 3, seconds: float = 1.5) -> harness.Cell:
    return harness.Cell("toy.sarvam", 1, "toy", CONFIG, "toy", dict(MIX),
                        {"check_requests": 8, "limits": dict(LIMITS)},
                        seed, seconds, False, toy.SPEC)
