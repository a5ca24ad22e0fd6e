"""95th percentile over the window's requests of (due -> HTTP reply) /
output tokens; a failed request misses."""

from benchmark.metrics import _tail


def read(obs):
    return _tail.percentile(obs, 95)
