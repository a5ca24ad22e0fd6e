"""Engine: mean wait from submit to admission (serving_queue_wait_seconds)."""

from benchmark.metrics import _hist


def read(obs):
    return _hist.mean_ms(obs, "serving_queue_wait_seconds")
