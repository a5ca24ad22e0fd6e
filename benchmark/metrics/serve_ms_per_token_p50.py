"""Median over the window's requests of (due -> HTTP reply) / output tokens."""

from benchmark.metrics import _tail


def read(obs):
    return _tail.percentile(obs, 50)
