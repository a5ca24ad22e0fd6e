"""Engine: mean time from a request's admission (prefill and adopt
dispatched, slot active) to its first token reaching the host:
``first_token - admitted`` of its ``serving.request`` span. The ``first``
event is fetched in dispatch order, behind the decode chunks queued before
it."""

from benchmark.metrics import _requests


def read(obs):
    return _requests.mean_gap_ms(obs, "admitted", "first_token")
