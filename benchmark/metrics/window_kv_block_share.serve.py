"""Engine: the blocks the window kind of cache holds over what it would
hold had nothing been given back (a block per ``block_t`` positions of
every row, as the full kind keeps them). Both ride on the
``serving.engine.dispatch`` regions; sums over the traced window."""

from benchmark.metrics import _mimo


def read(obs):
    stats = _mimo.dispatches(obs)
    whole = sum(int(s.get("window_blocks_unreleased", 0)) for s in stats)
    if whole <= 0:
        return None
    return 100.0 * sum(int(s["window_blocks"]) for s in stats) / whole
