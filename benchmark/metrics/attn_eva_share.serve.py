"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent under ``attn_eva`` (the heads laid
apart, the decode kernel over both kinds and the joining of its two
softmaxes; in prefill the gathers of the summaries and of the window's
earlier chunks, and ``chunk_attention``)."""

from benchmark.metrics import _eva


def read(obs):
    return _eva.share_of_programs(obs, "attn_eva")
