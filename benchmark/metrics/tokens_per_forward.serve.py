"""Engine: tokens a live slot's forward pass yields: ``revealed`` over
``forwards`` of the decode deliveries in the traced window. 0.8 on the
static schedule (4 of 5 passes reveal one position each); a threshold that
positions cross, or a commit merged into the next block's first pass,
raises it."""

from benchmark.metrics import _sdar


def read(obs):
    stats = _sdar.deliveries(obs)
    forwards = _sdar.total(stats, "forwards")
    if forwards <= 0:
        return None
    return _sdar.total(stats, "revealed") / forwards
