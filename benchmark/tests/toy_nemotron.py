"""A toy Nemotron cell for the CPU rehearsal: the ``nemotron_serve`` runner,
the open-loop generator and the readers at sizes a test can hold (a hidden
size of 128; Mamba-2 mixers of 8 heads of 16 over a state of 32 in 2
groups; one attention layer of 4 heads over 2 KV heads of 32; latent expert
layers of 16 experts of which 8 are held, top 4, in a latent of 32; pages
of 4; prefill chunks of 16, so that a prompt carries its states over several
chunks). The limit here was read at THESE sizes on the CPU and is not the
chip's (three seeds, 1.5 s windows, 8 requests a seed, 160 tokens checked;
``served_off_best_pct``): the program 1.9-2.5, the reference at bfloat16
3.1-5.0, the float8 control 26.3-33.1 (over all 160), and over the 60
tokens of the 3 shortest requests the state zeroed at every chunk
15.0-16.7, the window not carried 18.3-35.0, no ``D`` 98.3-100, no gate
98.3-100, no routed scaling 51.7-61.7: every fault separates at 10. The carry probe
(24 prompts of 17-19 positions, 3 tokens each, 72 checked; the same three
seeds, every variant over every prompt): the program 0-5.6, the reference
at bfloat16 1.4-4.2, the control 27.8-37.5, the state zeroed at every chunk
29.2-30.6, the window not carried 61.1-81.9: its limit 12 is their
geometric middle."""

from benchmark import harness

from . import toy

CONFIG = {
    "vocab_size": 2048, "hidden_size": 128, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 32, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "n_routed_experts": 8, "n_routed_experts_published": 16,
    "num_experts_per_tok": 4, "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 5, "norm_eps": 1e-5, "max_position_embeddings": 128,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "runners": {"nemotron_serve": {"slots": 4, "kv_blocks": 128, "kv_block_t": 4,
                                   "max_new_tokens": 20, "prefill_chunk": 16}},
}
MIX = {"runner": "nemotron_serve", "kind": "open_loop", "rate_rps": 6.0, "lead_in_s": 0.5,
       "prompt_len": {"kind": "lognormal", "median": 40, "sigma": 0.5, "min": 10, "max": 100}}
LIMITS = {"malformed_replies": 0, "served_off_best_pct": 10.0, "carry_off_best_pct": 12.0}
PROBE = {"requests": 24, "new_tokens": 3}


def cell(seed: int = 2**31 + 3, seconds: float = 1.5) -> harness.Cell:
    return harness.Cell("toy.nemotron", 1, "toy", CONFIG, "toy", dict(MIX),
                        {"check_requests": 8, "carry_probe": dict(PROBE),
                         "limits": dict(LIMITS)},
                        seed, seconds, False, toy.SPEC)
