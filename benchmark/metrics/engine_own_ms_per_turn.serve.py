"""Engine: the host's own Python a turn. Mean over the
``serving.engine.turn`` spans wholly inside the traced window of the turn
less its ``idle`` (empty queue), ``fetch`` (the device's tokens) and
``launch`` (calls into device programs, which block behind a full device
queue) regions."""

from benchmark.metrics import _launches


def read(obs):
    return _launches.ms_per_turn(obs, _launches.WAITS + (_launches.LAUNCH,), own=True)
