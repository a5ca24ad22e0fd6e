"""Engine: mean time from submit to the first token (serving_ttft_seconds).
The client cannot see it: the HTTP surface answers only at the end."""

from benchmark.metrics import _hist


def read(obs):
    return _hist.mean_ms(obs, "serving_ttft_seconds")
