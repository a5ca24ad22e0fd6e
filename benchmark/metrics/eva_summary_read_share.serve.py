"""Kernels: of the cache pages the decode dispatches' last steps fetch, the
share that are summaries (``summary_blocks_read`` over it and
``local_blocks_read``), summed over the ``serving.engine.dispatch`` regions
of the traced window: how much of what decode reads is EVA's remote part."""

from benchmark.metrics import _eva


def read(obs):
    stats = _eva.dispatches(obs)
    summary = sum(int(d["summary_blocks_read"]) for d in stats)
    both = summary + sum(int(d["local_blocks_read"]) for d in stats)
    if both <= 0:
        return None
    return 100.0 * summary / both
