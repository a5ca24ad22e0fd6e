"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent under ``attn_window`` (gather,
scores and context of the sliding-window layers, over a ring of blocks)."""

from benchmark.metrics import _mimo


def read(obs):
    return _mimo.share_of_programs(obs, "attn_window")
