"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent under ``attn_full`` (gather, scores
and context of the full-attention layers, over the block table's view)."""

from benchmark.metrics import _mimo


def read(obs):
    return _mimo.share_of_programs(obs, "attn_full")
