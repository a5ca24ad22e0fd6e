"""Mean device time of one decode step (one token for every slot)."""

from benchmark.metrics import _decode


def read(obs):
    s = _decode.step_seconds(obs)
    return None if s is None else 1000.0 * s
