"""Engine: mean wait of a request in the engine's pending deque for a free
slot (and arena blocks): ``admitted - dequeued`` of its ``serving.request``
span. ``queue_wait_ms.serve`` less this is the wait for the loop to come
round (``enqueued -> dequeued``)."""

from benchmark.metrics import _requests


def read(obs):
    return _requests.mean_gap_ms(obs, "dequeued", "admitted")
