"""Start-up: seconds inside ``ContinuousBatcher.prewarm`` (the engine's
``serving.engine.prewarm`` spans, one a call: every wave of dummies through
the production path, with the traces, lowerings, compiles or loads and the
first executions they cause)."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.prewarms(obs), _compiles.seconds)
