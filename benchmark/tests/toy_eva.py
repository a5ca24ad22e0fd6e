"""A toy EvaByte cell for the CPU rehearsal: the ``eva_serve`` runner, the
open-loop generator and the readers at sizes a test can hold (window 32,
chunks of 4: a prompt of 90 with 40 new tokens spans five windows). The
limit here was read at THESE sizes on the CPU (two seeds, 1 s windows:
program 0.001-0.007, float8 control 0.17-0.21, plain-mean pooling 0.47-0.77,
a stale roll-over 2.6-4.6, no summaries 3.7-4.6) and is not the chip's."""

from benchmark import harness

from . import toy

CONFIG = {
    "vocab_size": 320, "hidden_size": 128, "num_attention_heads": 4, "intermediate_size": 256,
    "num_hidden_layers": 2, "window_size": 32, "chunk_size": 4, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 256,
    "runners": {"eva_serve": {"slots": 4, "kv_blocks": 48, "kv_block_t": 4,
                              "max_new_tokens": 40, "prefill_chunk": 16}},
}
MIX = {"runner": "eva_serve", "kind": "open_loop", "rate_rps": 5.0, "lead_in_s": 0.5,
       "prompt_len": {"kind": "lognormal", "median": 40, "sigma": 0.7, "min": 6, "max": 200}}
LIMITS = {"malformed_replies": 0, "served_logit_gap_sd": 0.05}


def cell(seed: int = 2**31 + 3, seconds: float = 1.5) -> harness.Cell:
    return harness.Cell("toy.eva", 1, "toy", CONFIG, "toy", dict(MIX),
                        {"check_requests": 12, "limits": dict(LIMITS)},
                        seed, seconds, False, toy.SPEC)
