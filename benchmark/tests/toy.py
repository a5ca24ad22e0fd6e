"""Toy cells for the CPU rehearsal: the same runners, mixes and readers at
sizes a test can hold. The limits here were read at THESE sizes on the CPU
(program against reference: loss 5e-6, gradient 4e-3, change 1e-3; served
gap 0) and are not the chip's."""

import json
from pathlib import Path

from benchmark import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
for _kind in ("end_to_end", "per_layer"):      # a toy cell is in no metric's list:
    for _m in SPEC[_kind]:                     # ask every reader, each knows its kind
        _m.pop("workloads", None)
TRAIN_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.03, "change_norm_gap": 0.03}
ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
GPT = {"vocab_size": 512, "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
       "n_positions": 128,
       "runners": {"gpt_train": {"optimizer": ADAMW},
                   "gpt_serve": {"slots": 4, "kv_blocks": 32, "kv_block_t": 16,
                                 "max_new_tokens": 8}}}
# wide enough that the blocks, not the token's own embedding, decide the next
# token: at d 64 nothing flips an argmax and the serve control reads 0
GPT_SERVE = {"vocab_size": 2048, "n_embd": 256, "n_layer": 2, "n_head": 4, "n_inner": 512,
             "n_positions": 128, "runners": GPT["runners"]}
COMPOSITE = {"vocab_size": 250, "n_embd": 32, "n_layer": 4, "n_head": 4, "n_inner": 64,
             "n_positions": 16,
             "runners": {"composite_train": {"mesh": {"data": 1, "fsdp": 2, "model": 2},
                                             "lr": 1e-2, "vocab_size_run": 256}}}
CHAT = {"runner": "gpt_serve", "kind": "open_loop", "rate_rps": 6.0, "lead_in_s": 0.5,
        "prompt_len": {"kind": "lognormal", "median": 24, "sigma": 0.7, "min": 16, "max": 100}}


def cell(kind: str, seed: int = 2**31 + 3, seconds: float = 1.0) -> harness.Cell:
    if kind == "gpt_train":
        return harness.Cell("toy.train", 1, "toy", GPT, "toy",
                            {"runner": kind, "kind": "closed_loop_batches", "shape": [4, 128]},
                            {"limits": dict(TRAIN_LIMITS)}, seed, seconds, False, SPEC)
    if kind == "composite_train":
        return harness.Cell("toy.train4", 4, "toy", COMPOSITE, "toy",
                            {"runner": kind, "kind": "closed_loop_batches", "shape": [1, 2, 16]},
                            {"limits": dict(TRAIN_LIMITS)}, seed, seconds, False, SPEC)
    if kind == "gpt_serve":
        return harness.Cell("toy.serve", 1, "toy", GPT_SERVE, "toy", dict(CHAT),
                            {"check_requests": 6,
                             "limits": {"malformed_replies": 0, "served_logit_gap_sd": 0.05}},
                            seed, seconds, False, SPEC)
    raise ValueError(kind)
