"""Engine: what the engine thread's calls into device programs cost a turn.
Mean over the ``serving.engine.turn`` spans wholly inside the traced window
of the ``serving.engine.launch`` regions inside each. With
``engine_own_ms_per_turn.serve`` it is ``engine_host_ms_per_turn.serve``."""

from benchmark.metrics import _launches


def read(obs):
    return _launches.ms_per_turn(obs, (_launches.LAUNCH,), own=False)
