"""Executables compiled or loaded INSIDE the measured window (``backend``
``xla.compile`` spans that start in it): a shape that ``warm()`` missed
compiles for seconds or loads for tenths inside somebody's latency. The
counter covers the whole window of every run, traced or not; 0 in a sound
run."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.in_window(obs, _compiles.is_backend))
