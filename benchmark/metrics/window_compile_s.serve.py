"""Seconds of tracing, lowering and backend compile or load that START
inside the measured window (all three phases of ``xla.compile``)."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.in_window(obs), _compiles.seconds)
