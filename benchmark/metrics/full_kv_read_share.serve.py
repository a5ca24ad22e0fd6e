"""Kernels: the full-attention layers' pages that the decode dispatches
fetch, over every slot's whole row of the block table. A dispatch's
``full_blocks_read`` is the pages its live rows hold at its last step,
which is what the paged-attention kernel fetches there (each row its own
pages, nothing for a dead row); a program without that stat gathers every
slot's view, ``slots x view_blocks`` pages, and is counted so. Over ``slots
x max_blocks``, the denominator of ``decode_view_block_share.serve`` times
the slots, summed over the ``serving.engine.dispatch`` regions of the
traced window: 100 is a step that reads every slot's whole row."""

from benchmark.metrics import _mimo


def read(obs):
    fetched = whole = 0
    for stats in _mimo.dispatches(obs):
        if "max_blocks" not in stats:
            continue
        slots = int(stats["rows"]) // obs["decode_chunk"]
        whole += slots * int(stats["max_blocks"])
        fetched += int(stats.get("full_blocks_read", slots * int(stats["view_blocks"])))
    if whole <= 0:
        return None
    return 100.0 * fetched / whole
