"""Model step, serving: the share of the decode and prefill programs'
device time in the traced window spent under ``eva_summarise`` (a step
gathers every row's last chunk out of the local arena, pools it and
scatters the summaries of the rows that completed one; a prefill chunk
pools all of its whole chunks)."""

from benchmark.metrics import _eva


def read(obs):
    return _eva.share_of_programs(obs, "eva_summarise")
