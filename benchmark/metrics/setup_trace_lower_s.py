"""Start-up: seconds the host spent tracing programs to jaxprs and lowering
them to StableHLO before the window opened (the ``trace`` and ``lower``
``xla.compile`` spans that start before it). A warm set-up pays these in
full: the cache's key is made from the lowered module."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.before_opening(
        obs, lambda s: s.attributes.get("phase") in ("trace", "lower")), _compiles.seconds)
